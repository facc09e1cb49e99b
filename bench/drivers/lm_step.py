"""Driver: the group-mode robust training step of a dense decoder LM.

Set-up compiles the program's step (``launch.steps.jit_group_train_step``,
params and optimizer state donated), makes the weights on the device from
the seed, and drives that one compiled step through its first
``check_steps`` steps on fresh batches: these warm it up, and their loss,
first aggregate (AdamW's first moment over 1 - b1) and parameter change are
what the reference is compared with.  The window then drives the same
compiled step on from there, one batch made on the device per step, with
one step in flight ahead of the host.  Once the window has closed and the
device's peak memory is read, the program's state is freed and the plain
reference (``bench/reference/dense_lm.py``) repeats the check steps from
the same weights and batches.

Traffic keys: ``groups`` (k), ``seqs_per_group``, ``seq_len``, ``robust``
(aggregator, attack, attack_scale, byzantine, trim_multiplier, max_iters,
tol), ``adamw`` (peak_lr, warmup_steps, b1, b2, eps), ``check_steps``,
``trace_steps`` and ``limits``.
"""

from __future__ import annotations

import functools
import gc
import importlib
import math
import time

from bench import arith, harness
from bench.gen.tokens import lm_batch
from bench.reference import dense_lm

# configuration keys -> the program's ModelConfig fields
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "sliding_window": "sliding_window",
           "tie_word_embeddings": "tie_embeddings"}


def program_config(cj: dict):
    """The program's ModelConfig for this configuration: its published
    config with every size set from the configuration file."""
    import jax.numpy as jnp
    mod, attr = cj["program_config"].split(":")
    base = getattr(importlib.import_module(mod), attr)
    kw = {field: cj[key] for key, field in _FIELDS.items()}
    return base.with_(name=cj["name"], dtype=jnp.dtype(cj["compute_dtype"]),
                      param_dtype=jnp.dtype(cj["param_dtype"]), **kw)


def init_params(key, shapes):
    """The benchmark's weights, in the pytree ``shapes`` describes: norm
    scales 1, the embedding N(0, 1) and every matrix N(0, 1/fan_in), both
    truncated at two standard deviations; one key per leaf."""
    import jax
    import jax.numpy as jnp
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if name.endswith("['scale']"):
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        if name == "['embed']":
            fan_in = 1
        elif name.startswith("['layers']"):   # (layers, fan_in, ...)
            fan_in = s.shape[1]
        else:                                 # the output head (d, vocab)
            fan_in = s.shape[0]
        x = jax.random.truncated_normal(jax.random.fold_in(key, i), -2.0,
                                        2.0, s.shape, jnp.float32)
        out.append((x * fan_in ** -0.5).astype(s.dtype))
    return jax.tree.unflatten(treedef, out)


def _leaf_norms(leaves, scale=1.0):
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32) * scale)))
            for l in leaves]


class Feed:
    """The benchmark's weights, batches and step keys for one seed, made on
    the device; the program and the reference are both given these."""

    def __init__(self, cj: dict, tr: dict, seed: int, params_shapes):
        import jax
        import jax.numpy as jnp
        key = harness.seed_key(seed)
        self.k_params, self.k_data, self.k_steps = (
            jax.random.fold_in(key, i) for i in (1, 2, 3))
        self.make_params = jax.jit(functools.partial(
            init_params, shapes=params_shapes))
        self.make_batch = jax.jit(functools.partial(
            lm_batch, vocab_size=cj["vocab_size"], seq_len=tr["seq_len"],
            groups=tr["groups"], per_group=tr["seqs_per_group"]))
        self.fold = jax.jit(jax.random.fold_in)

        @jax.jit
        def change(params, kp):
            p0 = self.make_params(kp)
            return _leaf_norms([a.astype(jnp.float32) - b.astype(jnp.float32)
                                for a, b in zip(jax.tree.leaves(params),
                                                jax.tree.leaves(p0))])
        self._change = change

    def params(self):
        return self.make_params(self.k_params)

    def batch(self, i):
        return self.make_batch(self.k_data, i)

    def step_key(self, i):
        return self.fold(self.k_steps, i)

    def change_norms(self, params) -> list:
        """Per-leaf norm of ``params`` less the seed's initial weights."""
        return [float(x) for x in self._change(params, self.k_params)]


def reference_readings(feed: Feed, cj: dict, tr: dict, *,
                       precision: str = "f32",
                       half_batch: bool = False) -> dict:
    """The plain reference over the check steps from the seed's weights and
    batches: ``losses``, ``first`` (per-leaf norms of step 0's aggregate)
    and ``change`` (per-leaf norms of the parameters' change)."""
    n = tr["check_steps"]
    ref = dense_lm.train_steps(
        feed.params(), [feed.batch(i) for i in range(n)],
        [feed.step_key(i) for i in range(n)], cj, tr["robust"], tr["adamw"],
        precision=precision, half_batch=half_batch)
    return {"losses": ref["losses"], "first": ref["first_agg_norms"],
            "change": feed.change_norms(ref["params"])}


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """The numbers ``correct`` rests on, each beside its limit: the largest
    gap between the check steps' losses, and the worst leaf's gap between
    the norms of the first aggregate and of the parameters' change (leaves
    whose reference gradient is nought to rounding left out of the
    change).  A number whose limit is null is read but not compared.
    Returns ``(checks, {"gaps": every number, "worst_leaf": ...})``."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = harness.leaf_norm_gap(prog["first"], ref["first"])
    change_gap, change_leaf = harness.leaf_norm_gap(
        prog["change"], ref["change"],
        include=harness.nonzero_leaves(ref["first"]))
    gaps = {"loss_gap": loss_gap, "first_grad_gap": grad_gap,
            "change_gap": change_gap}
    checks = {name: (v, limits[name]) for name, v in gaps.items()
              if limits[name] is not None}
    return checks, {"gaps": gaps, "worst_leaf": {"first_grad": grad_leaf,
                                                 "change": change_leaf}}


def build(cj: dict, tr: dict):
    """The program's pieces for this configuration and traffic:
    ``(cfg, rc, optimizer, params shapes)``."""
    import jax
    from repro import optim
    from repro.core import RobustConfig
    from repro.models import model as model_lib

    cfg = program_config(cj)
    k, rob, adam = tr["groups"], tr["robust"], tr["adamw"]
    attack_kwargs = (() if rob["attack"] == "none"
                     else (("scale", rob["attack_scale"]),))
    rc = RobustConfig(num_workers=k, num_byzantine=rob["byzantine"],
                      num_batches=k, aggregator=rob["aggregator"],
                      attack=rob["attack"], attack_kwargs=attack_kwargs,
                      trim_multiplier=rob["trim_multiplier"],
                      gmom_max_iters=rob["max_iters"], gmom_tol=rob["tol"])
    optimizer = optim.adamw(
        optim.schedule.linear_warmup(adam["peak_lr"],
                                     warmup_steps=adam["warmup_steps"]),
        b1=adam["b1"], b2=adam["b2"], eps=adam["eps"])
    params_s = jax.eval_shape(lambda kk: model_lib.init(kk, cfg),
                              jax.random.PRNGKey(0))
    return cfg, rc, optimizer, params_s


def compile_step(cfg, rc, optimizer, params_s, feed: Feed):
    """The program's group step, jitted with params and optimizer state
    donated, compiled for this feed's shapes."""
    import jax
    import jax.numpy as jnp
    from repro.launch import steps
    opt_s = jax.eval_shape(optimizer.init, params_s)
    batch_s = jax.eval_shape(feed.batch, 0)
    jitted, _ = steps.jit_group_train_step(
        cfg, rc, optimizer, params_s, opt_s, batch_s, mesh=None,
        target_backend=jax.default_backend())
    return jitted.lower(params_s, opt_s, batch_s, feed.k_steps,
                        jnp.int32(0)).compile()


def make_stepper(compiled, feed: Feed):
    """``step(state, i) -> (state, loss)``: one call of the compiled step on
    batch ``i``, made on the device."""
    import jax.numpy as jnp

    def step(state, i):
        params, opt_state = state
        with harness.span("bench.make_batch"):
            batch = feed.batch(i)
        with harness.span("bench.dispatch"):
            params, opt_state, metrics = compiled(
                params, opt_state, batch, feed.step_key(i), jnp.int32(i))
        return (params, opt_state), metrics["loss_mean"]
    return step


def check_steps(step, optimizer, feed: Feed, tr: dict):
    """Drive the step from the seed's weights through the check steps.
    Returns ``(state, readings)`` with readings as ``reference_readings``
    gives them: the first aggregate is AdamW's first moment after one step
    over 1 - b1."""
    import jax
    b1 = tr["adamw"]["b1"]
    first_norms = jax.jit(lambda mu: _leaf_norms(jax.tree.leaves(mu),
                                                 1.0 / (1.0 - b1)))
    with harness.span("bench.init"):
        params = feed.params()
        state = (params, jax.jit(optimizer.init)(params))
    del params
    prog = {"losses": []}
    for i in range(tr["check_steps"]):
        state, loss = step(state, i)
        prog["losses"].append(float(loss))
        if i == 0:
            prog["first"] = [float(x) for x in first_norms(state[1].mu)]
    prog["change"] = feed.change_norms(state[0])
    return state, prog


def run(ctx: harness.Context) -> dict:
    import jax

    cj, tr = ctx.config, ctx.traffic
    cfg, rc, optimizer, params_s = build(cj, tr)
    feed = Feed(cj, tr, ctx.seed, params_s)
    tokens_per_step = tr["groups"] * tr["seqs_per_group"] * tr["seq_len"]
    with harness.span("bench.compile"):
        compiled = compile_step(cfg, rc, optimizer, params_s, feed)
    compiled_bytes = harness.compiled_bytes(compiled)
    ctx.log(f"[lm_step] compiled at {ctx.elapsed():.1f} s, "
            f"{compiled_bytes} B on the device")
    step = make_stepper(compiled, feed)
    state, prog = check_steps(step, optimizer, feed, tr)
    n_check = tr["check_steps"]
    setup_s = ctx.elapsed()
    ctx.log(f"[lm_step] set-up {setup_s:.2f} s; check losses "
            f"{prog['losses']}")

    # the window
    losses = []
    n_window = tr["trace_steps"] if ctx.trace else None
    with harness.traced(ctx) as trace:
        t0 = time.perf_counter()
        i, prev = n_check, None
        while True:
            state, loss = step(state, i)
            losses.append(loss)
            i += 1
            if prev is not None:
                with harness.span("bench.wait"):
                    prev.block_until_ready()
            prev = loss
            if n_window is not None:
                if len(losses) >= n_window:
                    break
            elif time.perf_counter() - t0 >= ctx.seconds:
                break
        with harness.span("bench.wait"):
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    failed = sum(not math.isfinite(x) for x in losses)
    peak = harness.memory_peak_bytes()
    ctx.log(f"[lm_step] window {window_s:.3f} s, {len(losses)} steps, "
            f"last loss {losses[-1]}")

    # free the program's state, then the reference
    del state, compiled, step, prev
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(feed, cj, tr)
    ctx.log(f"[lm_step] reference {time.perf_counter() - t_ref:.1f} s; "
            f"losses {ref['losses']}")
    checks, gaps = compare(prog, ref, tr["limits"])
    return {
        "setup_s": setup_s, "attempted": len(losses), "failed": failed,
        "e2e": {"train_tokens_per_s":
                len(losses) * tokens_per_step / window_s},
        "checks": checks, "memory_peak_bytes": peak,
        "trace_path": trace["path"],
        "counters": {"tokens": len(losses) * tokens_per_step,
                     "steps": len(losses), "window_s": window_s,
                     "flops_per_token": arith.dense_lm_train_flops_per_token(
                         cj, tr["seq_len"]),
                     "compiled_bytes": compiled_bytes},
        "readings": dict(gaps, program=prog, reference=ref),
    }
