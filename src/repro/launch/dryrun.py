"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes with 512 placeholder host devices.

For each pair this lowers the real step function — group-mode robust
train_step (train_4k), prefill forward (prefill_32k), or single-token
serve_step (decode_32k / long_500k) — with full-size ShapeDtypeStruct inputs
and the production shardings, compiles it, and records
``memory_analysis``/``cost_analysis``/collective bytes for §Dry-run and
§Roofline of EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]

The production meshes need 256/512 devices; on a CPU host the entry points
call :func:`force_host_device_count` BEFORE jax's backend initializes.  This
used to happen as an import-time ``os.environ`` mutation, which poisoned any
process that imported dryrun helpers after its own jax init (a later import
silently saw 512 virtual devices — or, worse, tests importing this module
for its helper API flipped the flag under an already-initialized backend).
Import is now side-effect free: callers that want the 512-device dry-run
environment invoke ``force_host_device_count`` explicitly (both CLI ``main``
entry points here and in ``repro.sim.sweep`` do), and everything else —
``lower_pair``/``dryrun_pair`` with an injected small mesh, the sweep's
comparison helpers, CI test collection — imports safely.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import sys
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHITECTURES, get_shape
from repro.configs.base import ModelConfig
from repro.core import RobustConfig, byzantine
from repro.launch import mesh as mesh_lib
from repro.launch import sharding, steps
from repro.roofline import analysis
from repro import optim

DEFAULT_HOST_DEVICE_COUNT = 512


def _jax_backend_initialized() -> bool:
    """True once jax has locked its device count (first backend init)."""
    try:
        from jax._src import xla_bridge as xb
    except Exception:  # pragma: no cover - private-API drift
        return False
    if hasattr(xb, "backends_are_initialized"):
        try:
            return bool(xb.backends_are_initialized())
        except Exception:  # pragma: no cover
            pass
    return bool(getattr(xb, "_backends", None))


def force_host_device_count(count: int = DEFAULT_HOST_DEVICE_COUNT) -> None:
    """Arm ``--xla_force_host_platform_device_count=<count>``.

    Must run before jax initializes its backend (jax locks the device count
    at first init).  Raises if the backend is already up with fewer devices
    than requested — the old import-time mutation failed silently in exactly
    this case.  No-op when the live backend already has enough devices
    (e.g. a subprocess that exported the flag itself).
    """
    if _jax_backend_initialized():
        if jax.device_count() >= count:
            return
        raise RuntimeError(
            f"jax backend already initialized with {jax.device_count()} "
            f"device(s); cannot force {count} host devices now.  Call "
            "force_host_device_count() before any jax device/array use, or "
            "export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{count} before starting python.")
    # Normalize rather than append: XLA_FLAGS may already carry the flag —
    # once (an exported =8 from a test shell), or several times (repeated
    # invocation under the old append logic, or a caller stacking exports).
    # XLA's flag parsing makes duplicate occurrences ambiguous, so strip
    # every occurrence and emit exactly one with the effective count (the
    # max of every pre-existing value and the request — a pre-existing
    # smaller count would make the production meshes fail later with a
    # confusing mesh-size error).  Repeated calls are idempotent: the
    # rewritten string is identical, including whitespace.
    flags = os.environ.get("XLA_FLAGS", "")
    flag_re = re.compile(
        r"--xla_force_host_platform_device_count=(\d+)")
    effective = max([int(v) for v in flag_re.findall(flags)] + [count])
    stripped = " ".join(flag_re.sub(" ", flags).split())
    os.environ["XLA_FLAGS"] = (
        stripped +
        f" --xla_force_host_platform_device_count={effective}").strip()


def _mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


@dataclasses.dataclass
class DryrunArtifacts:
    """Everything one lower+compile produces, for downstream consumers.

    ``repro.sim.sweep`` builds per-scenario collective-cost entries from
    these; ``dryrun_pair`` keeps its original record-only return."""
    arch: str
    shape_name: str
    mesh_name: str
    step_kind: str
    num_chips: int
    cfg: ModelConfig
    shape: object
    record: analysis.RooflineRecord
    lowered: object
    compiled: object
    compile_seconds: float


def default_train_rc(num_groups: int) -> RobustConfig:
    """The historical dry-run aggregation config (gmom + sign_flip)."""
    return RobustConfig(num_workers=num_groups, num_byzantine=1,
                        num_batches=num_groups, aggregator="gmom",
                        attack="sign_flip", gmom_max_iters=8)


def lower_pair(arch_or_cfg, shape_name: str, *, multi_pod: bool = False,
               mesh=None, num_groups: int = 4, microbatches: int = 1,
               fsdp: bool = True, rc: RobustConfig | None = None,
               schedule: byzantine.AttackSchedule | None = None,
               gather_grads: bool = False,
               verbose: bool = True) -> DryrunArtifacts:
    """Lower + compile one (arch, shape, mesh) and return all artifacts.

    ``rc`` injects the full aggregation pipeline configuration (aggregator,
    attack, round_backend, trim, ...) into the group-mode train step;
    ``schedule`` additionally threads a multi-round ``AttackSchedule``
    through the step (the lowered function then takes/returns the
    adversary's carried state).  Train shapes only; both default to the
    historical gmom + sign_flip dry-run configuration.

    ``gather_grads=True`` lowers the dense O(d)-per-device BASELINE: the
    stacked gradients are constrained fully replicated before aggregation
    (the gather the pre-shard-local code implied) and the aggregation runs
    with a trivial ShardSpec.  Default False keeps gradients partitioned
    over ``model`` end-to-end — the shard-local path whose peak memory the
    pod sweep's big-model cells gate against the gathered baseline.
    """
    if mesh is None:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    cfg, shape, batch = steps.input_specs(arch_or_cfg, shape_name,
                                          num_groups=num_groups)
    arch = arch_or_cfg if isinstance(arch_or_cfg, str) else cfg.name
    num_chips = mesh.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        params_s = steps.abstract_params(cfg)
        pshard = sharding.param_shardings(params_s, mesh, cfg, fsdp=fsdp)

        if shape.kind == "train":
            if rc is None:
                rc = default_train_rc(num_groups)
            opt = optim.adamw(3e-4)
            opt_s = steps.abstract_opt_state(opt, params_s)
            jitted, _ = steps.jit_group_train_step(
                cfg, rc, opt, params_s, opt_s, batch, mesh=mesh,
                gather_grads=gather_grads, microbatches=microbatches,
                schedule=schedule, fsdp=fsdp)
            key_s = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
            round_s = jax.ShapeDtypeStruct((), jax.numpy.int32)
            args = (params_s, opt_s, batch, key_s, round_s)
            if schedule is not None:
                args += (jax.eval_shape(schedule.init_state),)
            lowered = jitted.lower(*args)
            step_kind = "train_step"

        elif shape.kind == "prefill":
            bshard = jax.tree.map(
                lambda x: jax.NamedSharding(
                    mesh, P(*((sharding.serve_batch_spec(
                        mesh, shape.global_batch)[0],)
                        + (None,) * (len(x.shape) - 1)))),
                batch)
            step_fn = steps.make_prefill_step(cfg)
            jitted = jax.jit(step_fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_s, batch)
            step_kind = "prefill"

        else:  # decode
            tokens_s, positions_s, state_s = batch
            sshard = sharding.decode_state_shardings(
                state_s, mesh, cfg, shape.global_batch)
            bspec = sharding.serve_batch_spec(mesh, shape.global_batch)
            baxis = bspec[0] if len(bspec) else None
            tshard = jax.NamedSharding(mesh, P(baxis, None))
            posshard = jax.NamedSharding(mesh, P(baxis))
            step_fn = steps.make_serve_step(cfg)
            jitted = jax.jit(step_fn,
                             in_shardings=(pshard, sshard, tshard, posshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_s, state_s, tokens_s, positions_s)
            step_kind = "serve_step"

        compiled = lowered.compile()

    elapsed = time.time() - t0
    record = analysis.build_record(
        arch=arch, shape=shape, cfg=cfg, mesh_name=_mesh_name(mesh),
        num_chips=num_chips, step=step_kind, compiled=compiled)
    if verbose:
        mem = compiled.memory_analysis()
        print(f"[dryrun] {arch} × {shape_name} × {_mesh_name(mesh)} "
              f"({step_kind}) compiled in {elapsed:.1f}s")
        print(f"  memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  collectives: {record.collective_breakdown}")
        print(f"  roofline: compute={record.compute_term:.3e}s "
              f"memory={record.memory_term:.3e}s "
              f"collective={record.collective_term:.3e}s "
              f"-> {record.bottleneck}-bound "
              f"(useful-FLOPs ratio {record.useful_flops_ratio:.2f})")
    return DryrunArtifacts(
        arch=arch, shape_name=shape_name, mesh_name=_mesh_name(mesh),
        step_kind=step_kind, num_chips=num_chips, cfg=cfg, shape=shape,
        record=record, lowered=lowered, compiled=compiled,
        compile_seconds=elapsed)


def dryrun_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
                mesh=None, num_groups: int = 4, microbatches: int = 1,
                fsdp: bool = True, verbose: bool = True,
                rc: RobustConfig | None = None, schedule=None,
                gather_grads: bool = False,
                return_artifacts: bool = False):
    """Lower+compile one (arch, shape, mesh); returns a RooflineRecord.

    Thin wrapper over :func:`lower_pair` kept for the original CLI/record
    contract; pass ``return_artifacts=True`` for (record, lowered, compiled).
    """
    art = lower_pair(arch, shape_name, multi_pod=multi_pod, mesh=mesh,
                     num_groups=num_groups, microbatches=microbatches,
                     fsdp=fsdp, rc=rc, schedule=schedule,
                     gather_grads=gather_grads, verbose=verbose)
    if return_artifacts:
        return art.record, art.lowered, art.compiled
    return art.record


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCHITECTURES))
    p.add_argument("--shape", choices=["train_4k", "prefill_32k",
                                       "decode_32k", "long_500k"])
    p.add_argument("--all", action="store_true",
                   help="run every (arch × shape) pair")
    p.add_argument("--multi-pod", action="store_true",
                   help="use the 2×16×16 multi-pod mesh")
    p.add_argument("--num-groups", type=int, default=4,
                   help="k — number of gradient batches (train shapes)")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--no-fsdp", action="store_true")
    p.add_argument("--out", default=None, help="write JSON records here")
    args = p.parse_args(argv)

    # entry-point guard: the production meshes need 512 host devices; this
    # must NOT happen at import time (see module docstring).
    force_host_device_count(DEFAULT_HOST_DEVICE_COUNT)

    pairs = []
    if args.all:
        for arch in ARCHITECTURES:
            for shape in ("train_4k", "prefill_32k", "decode_32k",
                          "long_500k"):
                pairs.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            p.error("--arch and --shape required unless --all")
        pairs = [(args.arch, args.shape)]

    records, failures = [], []
    for arch, shape in pairs:
        try:
            records.append(dryrun_pair(
                arch, shape, multi_pod=args.multi_pod,
                num_groups=args.num_groups, microbatches=args.microbatches,
                fsdp=not args.no_fsdp))
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))

    if records:
        print()
        print(analysis.format_table(records))
    if args.out:
        analysis.save_records(records, args.out)
        print(f"\nwrote {len(records)} records to {args.out}")
    if failures:
        print(f"\nFAILURES ({len(failures)}):")
        for arch, shape, err in failures:
            print(f"  {arch} × {shape}: {err}")
        sys.exit(1)


if __name__ == "__main__":
    main()
