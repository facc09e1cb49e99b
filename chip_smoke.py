"""Smoke test of the Byzantine-robust training system on a TPU.

    python chip_smoke.py             # one chip: phases 1 and 2
    python chip_smoke.py --chips 4   # four chips: the sharded LM step only

Phase 1, the paper's server round: the linear-regression scenario at the
paper's geometry (m=50 workers, q=5 Byzantine, k=11 batches, d=100,
N=50,000) through ``sim.run_scenario``, once with ``round_backend="auto"``
(which must resolve to the fused Pallas round kernel on a TPU) and once
with the jnp ``reference`` pipeline.  The two final estimation errors must
agree and both sit below the paper's error scale ``paper_floor``.

Phase 2, the production LM step: ``repro.launch.train.main`` in
``--scale device`` mode runs minitron-4b's one-chip share (every published
width, 4 whole layers, 1/8 of the vocabulary) under gmom with k=4 groups,
q=1 sign-flipping group and AdamW at the entry point's lr (a linear
warmup).  Losses must be finite and fall at every step.

``--chips 4``: the same LM step on a 2x2 (data, model) mesh with the
production shardings, once with the stacked gradients partitioned over
``model`` (shard-local aggregation) and once gathered.  Then the step's
halves apart: each layout's stacked gradients, and the aggregation of one
set of gradients both ways.  Shard-local and gathered aggregation of the
same gradients must agree to f32 rounding; the layouts' gradients to bf16
rounding; the two steps' aggregates within what those gradients explain.

One process drives the chip and starts no other.  The script fails, and
prints no result line, when JAX finds no TPU, when the repository is not
beside it, or when any check misses.  The last line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))

# fused and reference lowerings of the same 40 GD rounds: they differ in
# summation order at f32 and plain GD contracts the difference.
EST_ERROR_RTOL = 1e-3
# Four chips, norms relative to the gathered layout's.  The two layouts
# partition the backward differently, so bf16 partial sums of a gradient
# are added in another order; a layout that drops or double-counts a data
# shard's part is off by about half the gradient.
GRAD_RTOL = 5e-2
# The same reported gradients aggregated shard-local and gathered: only the
# order of f32 reductions differs, which moves the Weiszfeld iterate by
# about 1e-6 and flips the bf16 rounding of a few entries of the result.
# Weights from partial distances of one shard move every entry.
AGG_RTOL = 1e-3
AGG_DIFFER_MAX = 1e-2   # share of the aggregate's entries
# The two whole steps: the aggregates inherit the gradients' difference.
STEP_AGG_RTOL = 1e-2


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def paper_round() -> None:
    from repro import sim
    from repro.core import aggregators

    base = sim.get_scenario("linreg/gmom/sign_flip/rotating")
    sc = dataclasses.replace(base, name="linreg/gmom/sign_flip/paper_m50",
                             num_workers=50, num_byzantine=5, num_batches=11,
                             dim=100, total_samples=50_000)
    backend = aggregators.resolve_round_backend(
        "auto", num_batches=sc.num_batches, total_dim=sc.dim,
        num_workers=sc.num_workers)
    print(f"[phase1] auto round backend: {backend}")
    check(backend == "fused", f"auto resolved {backend!r}, not 'fused'")
    fused = sim.run_scenario(sc, round_backend="auto")
    ref = sim.run_scenario(sc, round_backend="reference")
    a, b = fused["final_est_error"], ref["final_est_error"]
    print(f"[phase1] m=50 q=5 k=11 d=100: final_est_error fused={a!r} "
          f"reference={b!r} paper_floor={sc.paper_floor!r}")
    check(math.isfinite(a) and math.isfinite(b), "non-finite error")
    check(abs(a - b) <= EST_ERROR_RTOL * abs(b),
          f"fused and reference disagree beyond rtol {EST_ERROR_RTOL}")
    check(max(a, b) < sc.paper_floor, "estimation error above paper_floor")


def lm_step() -> None:
    from repro.launch import train

    # the lr is the entry point's own for --scale device: a linear warmup
    # to 1e-3 over train.DEVICE_WARMUP_STEPS steps
    res = train.main(["--scale", "device", "--arch", "minitron-4b",
                      "--steps", "6", "--num-batches", "4", "--byzantine", "1",
                      "--attack", "sign_flip", "--aggregator", "gmom",
                      "--batch", "4", "--seq-len", "4096", "--seed", "0"])
    losses = [h["loss_mean"] for h in res["history"]]
    print(f"[phase2] {res['arch']}: {res['params']} params, "
          f"{res['tokens_per_step']} tokens/step, lr {res['lr']!r} warmed "
          f"up over {res['warmup_steps']} steps, losses={losses}")
    print(f"[phase2] compile_s={res['compile_seconds']!r} "
          f"step_s={res['step_seconds']!r} "
          f"peak_bytes_in_use={res['peak_bytes_in_use']!r} "
          f"compiled_temp_bytes={res['compiled_temp_bytes']!r}")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          "loss did not fall at every step")


def four_chip_step() -> dict:
    """Runs the four-chip checks.  Returns what a caller needs to aggregate
    the same gradients another way and hold the result to the same
    comparison: ``mesh``, ``rc``, ``key``, ``layouts``, ``pshard``, the
    gathered ``gradients`` in the shard-local layout, their gathered
    ``aggregate`` and ``compare``."""
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.configs import get_chip_share
    from repro.core import RobustConfig
    from repro.core.robust_train import aggregate_reported
    from repro.data.tokens import TokenStream
    from repro.launch import sharding, steps, train
    from repro.launch.mesh import make_mesh
    from repro.models import model as model_lib

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    cfg = get_chip_share("minitron-4b")
    k = 4
    rc = RobustConfig(num_workers=k, num_byzantine=1, num_batches=k,
                      attack="sign_flip", aggregator="gmom")
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=4096,
                         global_batch=2 * k, num_workers=k, seed=0)
    # One step at a constant lr 1e-3, so that the update moves a weight by
    # several bf16 ulps and the parameter comparison can see it.
    b1 = 0.9
    optimizer = optim.adamw(1e-3, b1=b1)

    @jax.jit
    def compare(a, b):
        """|a - b| / |b| over two pytrees in f32, and how many entries
        differ."""
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        pairs = list(zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        d2 = sum(jnp.sum((f32(x) - f32(y)) ** 2) for x, y in pairs)
        b2 = sum(jnp.sum(f32(y) ** 2) for _, y in pairs)
        n = sum(jnp.sum(x != y).astype(jnp.float32) for x, y in pairs)
        return jnp.sqrt(d2 / b2), n

    def rel(a, b):
        r, n = compare(a, b)
        return float(r), int(n)

    runs = {}
    for gather in (False, True):
        name = "gathered" if gather else "shard-local"
        runs[name] = run = train.run_device_steps(
            cfg, rc, optimizer, stream, steps=1, seed=0, mesh=mesh,
            gather_grads=gather)
        spans = {len(x.sharding.device_set)
                 for x in jax.tree.leaves(run["params"])}
        print(f"[4chip] {name} step: "
              f"loss={run['history'][0]['loss_mean']!r} "
              f"agg_grad_norm={run['history'][0]['agg_grad_norm']!r} "
              f"compile_s={run['compile_seconds']!r} "
              f"step_s={run['step_seconds']!r} "
              f"peak_bytes_in_use={run['peak_bytes_in_use']!r} "
              f"param arrays span {sorted(spans)} devices")
        check(spans == {4}, f"{name} params span {spans} devices, not 4")
        n_bad = sum(int(jnp.sum(~jnp.isfinite(x.astype(jnp.float32))))
                    for x in jax.tree.leaves(run["params"]))
        check(n_bad == 0, f"{name}: {n_bad} non-finite params")
    local, gathered = runs.pop("shard-local"), runs.pop("gathered")
    n_params = sum(x.size for x in jax.tree.leaves(local["params"]))
    param_rel, param_n = rel(local["params"], gathered["params"])
    # AdamW's first moment after one step is (1 - b1) times the aggregate.
    step_mu = {"shard-local": local["opt_state"].mu,
               "gathered": gathered["opt_state"].mu}
    step_rel, _ = rel(step_mu["shard-local"], step_mu["gathered"])
    print(f"[4chip] after one step the parameters differ: |diff|/|param|="
          f"{param_rel!r}, {param_n} of {n_params} entries; the steps' "
          f"aggregates |diff|/|agg|={step_rel!r}")
    del local, gathered

    # Where the gap starts: the first step's stacked group gradients from
    # each layout's backward, then the aggregation of each.
    params_s = jax.eval_shape(lambda key: model_lib.init(key, cfg),
                              jax.random.PRNGKey(0))
    pshard = sharding.param_shardings(params_s, mesh, cfg)
    bshard = sharding.batch_shardings(jax.eval_shape(stream.batch, 0), mesh)
    rep = sharding.replicated(mesh)
    params = jax.jit(lambda key: model_lib.init(key, cfg),
                     out_shardings=pshard)(jax.random.PRNGKey(0))
    batch = jax.jit(stream.batch, out_shardings=bshard)(0)
    key = jax.device_put(train.device_step_key(0, 0), rep)
    layouts = {name: steps.group_grad_layout(params_s, mesh, cfg,
                                             gather_grads=gather)
               for name, gather in (("shard-local", False),
                                    ("gathered", True))}

    def aggregate(name):
        gshard, spec = layouts[name]

        def agg(grads, key):
            reported, _ = steps.report_groups(grads, rc, key, 0)
            return aggregate_reported(reported, rc, key=key,
                                      shard_spec=spec)
        return jax.jit(agg, in_shardings=(gshard, rep), out_shardings=pshard)

    # under the mesh, as the step runs: the model picks its
    # sequence-parallel regions from it
    with jax.set_mesh(mesh):
        grads = {name: jax.jit(steps.make_group_grads(cfg),
                               in_shardings=(pshard, bshard),
                               out_shardings=(rep, gshard))(params, batch)[1]
                 for name, (gshard, _) in layouts.items()}
        del params, batch
        aggs = {name: aggregate(name)(grads[name], key) for name in layouts}
        same_grads = jax.device_put(grads["gathered"],
                                    layouts["shard-local"][0])
        same = aggregate("shard-local")(same_grads, key)
    grad_rel, grad_n = rel(grads["shard-local"], grads["gathered"])
    n_grads = sum(x.size for x in jax.tree.leaves(grads["gathered"]))
    agg_rel, _ = rel(aggs["shard-local"], aggs["gathered"])
    same_rel, same_n = rel(same, aggs["gathered"])
    faithful = {name: rel(aggs[name], jax.tree.map(
        lambda m: m / (1 - b1), step_mu[name]))[0] for name in layouts}
    print(f"[4chip] stacked gradients, shard-local vs gathered backward: "
          f"|diff|/|grad|={grad_rel!r}, {grad_n} of {n_grads} entries "
          f"differ")
    print(f"[4chip] aggregates of those gradients |diff|/|agg|={agg_rel!r}; "
          f"of the same (gathered) gradients, shard-local vs gathered "
          f"aggregation: {same_rel!r}, {same_n} of {n_params} entries "
          f"differ; these aggregates against the steps' own: {faithful!r}")
    check(grad_rel <= GRAD_RTOL,
          f"the layouts' gradients differ beyond {GRAD_RTOL}")
    check(same_rel <= AGG_RTOL and same_n <= AGG_DIFFER_MAX * n_params,
          f"shard-local aggregation of the same gradients differs from "
          f"gathered beyond {AGG_RTOL} or in more than {AGG_DIFFER_MAX} of "
          f"its entries")
    check(step_rel <= STEP_AGG_RTOL,
          f"the steps' aggregates differ beyond {STEP_AGG_RTOL}")
    return {"mesh": mesh, "rc": rc, "key": key, "layouts": layouts,
            "pshard": pshard, "gradients": same_grads,
            "aggregate": aggs["gathered"], "compare": rel}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch import train

    train.use_compile_cache()
    device = train.device_description()
    print(f"[smoke] devices: {device}")
    if device["platform"] != "tpu":
        print("[smoke] no TPU found; nothing was run", file=sys.stderr)
        return 1
    if args.chips == 4:
        four_chip_step()
    else:
        paper_round()
        lm_step()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
