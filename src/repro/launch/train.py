"""End-to-end training driver.

Three modes:

* ``--scale cpu`` (default): reduced config, synthetic token stream,
  worker-mode Byzantine GD (the paper-faithful path), checkpointing,
  metrics log.
* ``--scale device``: the production group-mode step
  (``steps.make_group_train_step``) with real arrays on the local device,
  at the architecture's one-chip share (``configs.get_chip_share``): every
  published width, depth and vocabulary cut as its config file states.
* ``--scale pod``: builds the production 16×16 (or 2×16×16) job with the
  group-mode step and full-size config, and lowers it on 512 virtual CPU
  devices (a dry run: nothing executes).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b \
        --steps 50 --byzantine 2 --attack sign_flip --aggregator gmom
    PYTHONPATH=src python -m repro.launch.train --scale device \
        --steps 6 --num-batches 4 --byzantine 1 --batch 4 --seq-len 4096
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import checkpoint, optim
from repro.core import (RobustConfig, aggregators, byzantine,
                        init_train_state, make_run_rounds,
                        restore_train_state, save_train_state,
                        schedule_from_config, staleness)
from repro.core.train_state import advance, history_rows
from repro.configs import ARCHITECTURES, get_chip_share, get_config
from repro.data.tokens import TokenStream
from repro.launch import steps as steps_lib
from repro.models import model as model_lib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
# At minitron-4b's widths a constant AdamW lr of 1e-4 or more throws the
# loss after the first step above its uniform-guess value, with or without
# an attack; warming up to 1e-3 over 1000 steps (1e-6 more each step), the
# loss falls at every step (PERF.md, PR 11 findings).
DEVICE_WARMUP_STEPS = 1000


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at one fixed path, which is
    part of the cache key: the directory ``JAX_COMPILATION_CACHE_DIR``
    names when it is set (JAX reads it itself), else ``<repo>/.jax_cache``.
    Called by the command-line entry points (``python -m
    repro.launch.train``, ``chip_smoke.py``), never at import and never by
    ``main(argv)``, which tests call in-process."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def device_description() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes_in_use():
    """Largest ``peak_bytes_in_use`` over the local devices, or None where
    the backend keeps no memory statistics."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def learning_rate(args):
    """``--lr``, reached by a linear warmup over ``--warmup-steps``."""
    if not args.warmup_steps:
        return args.lr
    return optim.schedule.linear_warmup(args.lr,
                                        warmup_steps=args.warmup_steps)


def build_cpu_batch(cfg, stream: TokenStream, step: int, key):
    batch = stream.batch(step)
    m, bw = batch["tokens"].shape[:2]
    if cfg.family == "vlm":
        t = batch["tokens"].shape[-1]
        keep = t - cfg.num_patches
        batch = {"tokens": batch["tokens"][..., :keep],
                 "labels": batch["labels"][..., :keep],
                 "patches": jax.random.normal(
                     key, (m, bw, cfg.num_patches, cfg.d_model), cfg.dtype)}
    elif cfg.family == "audio":
        t_enc = max(batch["tokens"].shape[-1] // cfg.encoder_seq_divisor, 1)
        batch = dict(batch, frames=jax.random.normal(
            key, (m, bw, t_enc, cfg.d_model), cfg.dtype))
    return batch


def resume_train_state(ckpt_dir, params, opt_state, schedule, step_key,
                       arrival=None):
    """Restore the latest checkpoint in ``ckpt_dir`` into a TrainState.

    Returns ``(state, restored_step)`` — ``(fresh state, 0)`` when there is
    no checkpoint.  format_version>=2 checkpoints restore the FULL state
    (params + opt_state + attack_state + round + key + metrics history), so
    the resumed trajectory is bit-identical to an uninterrupted run.
    Legacy params-only checkpoints take a one-shot compatibility path:
    params are restored (with dtype casting, as the old restore did),
    everything else reinitializes, and a loud warning says so; the next
    save writes the full state.
    """
    state = init_train_state(params, opt_state, step_key, schedule=schedule,
                             arrival=arrival)
    step = checkpoint.latest_step(ckpt_dir) if ckpt_dir else None
    if step is None:
        return state, 0
    manifest = checkpoint.read_manifest(ckpt_dir, step)
    if manifest.get("payload") == "train_state":
        state = restore_train_state(ckpt_dir, step, params, opt_state,
                                    schedule=schedule, arrival=arrival,
                                    manifest=manifest)
        print(f"[train] restored full TrainState (round {step}, "
              f"schedule {schedule.name!r}) from {ckpt_dir}")
        return state, step
    # legacy v1 checkpoints and bare params trees saved via checkpoint.save
    params = checkpoint.restore(ckpt_dir, step, params, allow_cast=True)
    print(f"[train] WARNING: legacy params-only checkpoint (step {step}, "
          f"{ckpt_dir}): optimizer state, adversary state, and metrics "
          "history were not saved and restart fresh — the resumed "
          "trajectory will NOT match an uninterrupted run. The next "
          "checkpoint upgrades to a full TrainState.")
    return state._replace(params=params,
                          round_index=jnp.asarray(step, jnp.int32)), step


def train_cpu(args) -> dict:
    cfg = get_config(args.arch).reduced()
    m = args.workers
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.batch, num_workers=m,
                         seed=args.seed)
    rc = RobustConfig(num_workers=m, num_byzantine=args.byzantine,
                      attack=args.attack, aggregator=args.aggregator,
                      num_batches=args.num_batches,
                      round_backend=args.round_backend,
                      arrival=args.arrival,
                      staleness_bound=args.staleness_bound)
    opt = optim.adamw(learning_rate(args))
    loss_fn = lambda p, b: model_lib.loss_fn(p, b, cfg)  # noqa: E731
    if args.schedule:
        schedule = byzantine.make_schedule(
            args.schedule, num_workers=m, num_byzantine=args.byzantine,
            attack=args.attack)
    else:
        schedule = schedule_from_config(rc)
    # Scan-compiled multi-round runner: rounds run in chunks of
    # --scan-chunk, each chunk a single XLA dispatch (the Python loop only
    # handles logging and checkpoint boundaries).
    arrival = staleness.arrival_from_config(rc)
    run = make_run_rounds(loss_fn, opt, rc, schedule=schedule,
                          arrival=arrival)

    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init(key, cfg)
    opt_state = opt.init(params)
    step_key = jax.random.fold_in(key, 10_000)
    # NOTE: resume assumes the same --seed/--batch/--seq-len (the data
    # stream re-derives from args); the step keys themselves are restored
    # from the checkpoint.
    state, start = resume_train_state(args.ckpt_dir, params, opt_state,
                                      schedule, step_key, arrival=arrival)

    chunk = max(1, args.scan_chunk)
    if args.ckpt_dir:
        chunk = min(chunk, args.ckpt_every)
    t0 = time.time()
    i = start
    while i < args.steps:
        n = min(chunk, args.steps - i)
        if args.ckpt_dir:   # never scan across a checkpoint boundary
            n = min(n, args.ckpt_every - i % args.ckpt_every)
        rounds = [build_cpu_batch(cfg, stream, j, jax.random.fold_in(key, j))
                  for j in range(i, i + n)]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *rounds)
        state, _ = advance(run, state, batch, per_round_batches=True)
        i += n
        if (i - 1) % args.log_every < n or i == args.steps:
            print(f"[train] step {i - 1:4d} loss_median="
                  f"{float(state.history['loss_median'][-1]):.4f} "
                  f"gnorm={float(state.history['agg_grad_norm'][-1]):.3f} "
                  f"({time.time() - t0:.1f}s)")
        # boundary saves plus a final save, so the completed run is always
        # resumable/inspectable even when steps % ckpt_every != 0
        if args.ckpt_dir and (i % args.ckpt_every == 0 or i == args.steps):
            save_train_state(args.ckpt_dir, state)
    history = history_rows(state.history)
    result = {"arch": args.arch, "aggregator": args.aggregator,
              "attack": args.attack, "byzantine": args.byzantine,
              "schedule": schedule.name,
              "arrival": args.arrival,
              "staleness_bound": args.staleness_bound,
              "resumed_from": start,
              "final_loss": history[-1]["loss_median"] if history else None,
              "first_loss": history[0]["loss_median"] if history else None,
              "history": history}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def device_step_key(seed: int, step: int):
    """The PRNG key of step ``step`` of :func:`run_device_steps`: it draws
    the Byzantine groups and the attack's and aggregator's randomness."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), 10_000), step)


def run_device_steps(cfg, rc: RobustConfig, optimizer, stream: TokenStream,
                     *, steps: int, seed: int, mesh=None,
                     gather_grads: bool = False) -> dict:
    """Run ``steps`` group-mode robust steps on the local devices.

    Parameters are initialised on the device from ``seed``; each step's
    batch is ``stream.batch(step)``, made on the device.  ``mesh`` (None:
    one device) and ``gather_grads`` select the layout as in
    ``steps.jit_group_train_step``.  The step is compiled ahead of time, so
    ``compile_seconds`` is set-up and each of ``step_seconds`` is one step
    waited out with ``block_until_ready``.  Returns the final params and
    optimizer state with the per-step metrics and timings."""
    key = jax.random.PRNGKey(seed)
    params_s = jax.eval_shape(lambda k: model_lib.init(k, cfg), key)
    opt_s = jax.eval_shape(optimizer.init, params_s)
    batch_s = jax.eval_shape(stream.batch, 0)
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        jitted, shard = steps_lib.jit_group_train_step(
            cfg, rc, optimizer, params_s, opt_s, batch_s, mesh=mesh,
            gather_grads=gather_grads, target_backend=jax.default_backend())
        pshard, oshard, bshard = (None, None, None) if shard is None \
            else shard[:3]
        params = jax.jit(lambda k: model_lib.init(k, cfg),
                         out_shardings=pshard)(key)
        opt_state = jax.jit(optimizer.init, out_shardings=oshard)(params)
        make_batch = jax.jit(stream.batch, out_shardings=bshard)
        place = ((lambda x: x) if shard is None
                 else (lambda x: jax.device_put(x, shard[3])))
        t0 = time.perf_counter()
        compiled = jitted.lower(params_s, opt_s, batch_s,
                                device_step_key(seed, 0),
                                jnp.int32(0)).compile()
        compile_seconds = time.perf_counter() - t0
        history, step_seconds = [], []
        for i in range(steps):
            batch = jax.block_until_ready(make_batch(i))
            t0 = time.perf_counter()
            params, opt_state, metrics = compiled(
                params, opt_state, batch,
                place(device_step_key(seed, i)), place(jnp.int32(i)))
            jax.block_until_ready((params, opt_state, metrics))
            step_seconds.append(time.perf_counter() - t0)
            history.append({k: float(v) for k, v in metrics.items()})
    return {"params": params, "opt_state": opt_state, "history": history,
            "compile_seconds": compile_seconds,
            "step_seconds": step_seconds,
            "peak_bytes_in_use": peak_bytes_in_use(),
            "memory_analysis": compiled.memory_analysis()}


def train_device(args) -> dict:
    """The production group-mode step on the local device at the
    architecture's one-chip share (``--scale device``)."""
    cfg = get_chip_share(args.arch)
    k = args.num_batches or 4
    rc = RobustConfig(num_workers=k, num_byzantine=args.byzantine,
                      num_batches=k, attack=args.attack,
                      aggregator=args.aggregator,
                      round_backend=args.round_backend)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.batch, num_workers=k,
                         seed=args.seed)
    run = run_device_steps(cfg, rc, optim.adamw(learning_rate(args)), stream,
                           steps=args.steps, seed=args.seed)
    mem = run["memory_analysis"]
    result = {"arch": cfg.name, "aggregator": args.aggregator,
              "attack": args.attack, "num_batches": k,
              "byzantine": args.byzantine, "lr": args.lr,
              "warmup_steps": args.warmup_steps,
              "device": device_description(),
              "params": int(sum(x.size for x in
                                jax.tree.leaves(run["params"]))),
              "tokens_per_step": args.batch * args.seq_len,
              "compile_seconds": run["compile_seconds"],
              "step_seconds": run["step_seconds"],
              "peak_bytes_in_use": run["peak_bytes_in_use"],
              "compiled_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
              "history": run["history"]}
    for i, (h, dt) in enumerate(zip(run["history"], run["step_seconds"])):
        print(f"[train] step {i} loss_mean={h['loss_mean']:.6f} "
              f"agg_grad_norm={h['agg_grad_norm']:.6f} "
              f"weiszfeld_iters={int(h['weiszfeld_iters'])} step_s={dt:.6f}")
    print(f"[train] {cfg.name} on {result['device']}: "
          f"compile_s={run['compile_seconds']:.3f} "
          f"peak_bytes_in_use={run['peak_bytes_in_use']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def train_pod(args):
    from repro.launch import dryrun
    # dryrun no longer forces the 512 virtual host devices at import time;
    # arm the flag explicitly before the first backend init.
    dryrun.force_host_device_count()
    rec = dryrun.dryrun_pair(args.arch, "train_4k",
                             multi_pod=args.multi_pod,
                             num_groups=args.num_batches or 4,
                             microbatches=args.microbatches)
    print("[train] pod-scale step compiled; roofline:",
          json.dumps(rec.to_dict(), indent=1, default=str))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="minitron-4b", choices=list(ARCHITECTURES))
    p.add_argument("--scale", default="cpu",
                   choices=["cpu", "device", "pod"])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--byzantine", type=int, default=2)
    p.add_argument("--num-batches", type=int, default=None, dest="num_batches")
    p.add_argument("--attack", default="sign_flip",
                   choices=byzantine.available())
    p.add_argument("--schedule", default=None,
                   choices=byzantine.available_schedules(),
                   help="multi-round attack schedule (default: rotating)")
    p.add_argument("--scan-chunk", type=int, default=10, dest="scan_chunk",
                   help="rounds fused into one lax.scan dispatch")
    p.add_argument("--round-backend", default="auto", dest="round_backend",
                   choices=["auto", "fused", "fused_interpret", "reference"],
                   help="gmom hot-path lowering: fused Pallas round kernel "
                        "vs jnp reference (auto: fused on TPU)")
    p.add_argument("--aggregator", default="gmom",
                   choices=aggregators.available())
    p.add_argument("--arrival", default="all_sync",
                   choices=staleness.available_arrivals(),
                   help="arrival model: which workers report fresh each "
                        "round (docs/ASYNC.md); stale workers contribute "
                        "their bounded-staleness buffered gradient")
    p.add_argument("--staleness-bound", type=int, default=0,
                   dest="staleness_bound",
                   help="max buffered-gradient age tau (0 with all_sync = "
                        "the paper's synchronous path, bit-identical)")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--warmup-steps", type=int, default=None,
                   dest="warmup_steps",
                   help="linear lr warmup to --lr over this many steps (0: "
                        "constant; default 1000 with --scale device, else "
                        "0): AdamW's first steps move every weight by about "
                        "the lr, which at published widths diverges")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    if args.warmup_steps is None:
        args.warmup_steps = DEVICE_WARMUP_STEPS if args.scale == "device" else 0
    if args.scale == "pod":
        # train_pod arms the 512 virtual host devices itself
        # (dryrun.force_host_device_count) — no pre-set XLA_FLAGS needed.
        return train_pod(args)
    if args.scale == "device":
        return train_device(args)
    return train_cpu(args)


if __name__ == "__main__":
    use_compile_cache()
    main()
