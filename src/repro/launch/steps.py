"""Production-scale train / serve steps and their abstract input specs.

Two gradient paths implement the paper's Algorithm 2 (DESIGN.md §4):

* **worker mode** (repro.core.robust_train) — one gradient per worker,
  attack at worker granularity.  Faithful to the paper line-by-line; used at
  experiment scale (the stacked (m, P) gradients are the paper server's
  O(md) memory, impossible at 72B+).
* **group mode** (here) — gradients computed directly per batch-group:
  mean-of-means == pooled mean, so the k honest batch means are identical to
  worker mode's (tests assert this), while peak memory drops from (m, P) to
  (k, P) with the 2D param layout preserved.  Byzantine corruption is
  injected at batch-mean granularity — exactly the quantity the analysis
  bounds (at most q of k batches contaminated).  This is the path the
  512-chip dry-run and the multi-pod scenario sweep (repro.sim.sweep)
  lower; aggregation dispatches through the registry
  (robust_train.aggregate_reported), so rc.aggregator / rc.round_backend /
  an optional AttackSchedule are all first-class here.

``input_specs`` provides ShapeDtypeStruct stand-ins for every model input —
weak-type-correct, shardable, no device allocation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_shape, long_context_variant
from repro.configs.base import InputShape, ModelConfig
from repro.core import RobustConfig, byzantine
from repro.core.robust_train import aggregate_reported
from repro.launch import sharding
from repro.models import blocks
from repro.models import model as model_lib


# ---------------------------------------------------------------------------
# batch construction

def train_batch_struct(cfg: ModelConfig, shape: InputShape, num_groups: int):
    """Abstract train batch: leaves (k, B/k, ...)."""
    k = num_groups
    if shape.global_batch % k != 0:
        raise ValueError(f"global_batch={shape.global_batch} % k={k} != 0")
    bg = shape.global_batch // k
    T = shape.seq_len
    i32 = jnp.int32

    def arr(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt)

    if cfg.family == "vlm":
        t_text = T - cfg.num_patches
        return {
            "tokens": arr((k, bg, t_text), i32),
            "labels": arr((k, bg, t_text), i32),
            "patches": arr((k, bg, cfg.num_patches, cfg.d_model), cfg.dtype),
        }
    if cfg.family == "audio":
        t_enc = max(T // cfg.encoder_seq_divisor, 1)
        return {
            "tokens": arr((k, bg, T), i32),
            "labels": arr((k, bg, T), i32),
            "frames": arr((k, bg, t_enc, cfg.d_model), cfg.dtype),
        }
    return {"tokens": arr((k, bg, T), i32), "labels": arr((k, bg, T), i32)}


def prefill_batch_struct(cfg: ModelConfig, shape: InputShape):
    B, T = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def arr(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt)

    if cfg.family == "vlm":
        return {"tokens": arr((B, T - cfg.num_patches), i32),
                "patches": arr((B, cfg.num_patches, cfg.d_model), cfg.dtype)}
    if cfg.family == "audio":
        t_enc = max(T // cfg.encoder_seq_divisor, 1)
        return {"tokens": arr((B, T), i32),
                "frames": arr((B, t_enc, cfg.d_model), cfg.dtype)}
    return {"tokens": arr((B, T), i32)}


def decode_input_struct(cfg: ModelConfig, shape: InputShape):
    """(tokens, positions, state) for one serve_step against a seq_len-deep
    context."""
    B, T = shape.global_batch, shape.seq_len
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    positions = jax.ShapeDtypeStruct((B,), jnp.int32)
    state = jax.eval_shape(
        lambda: model_lib.init_decode_state(cfg, B, T))
    return tokens, positions, state


def input_specs(arch_or_cfg, shape_name: str, *, num_groups: int = 4):
    """The dry-run entry: abstract inputs for (arch, shape)."""
    cfg = (arch_or_cfg if isinstance(arch_or_cfg, ModelConfig)
           else get_config(arch_or_cfg))
    shape = get_shape(shape_name)
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    if shape.kind == "train":
        return cfg, shape, train_batch_struct(cfg, shape, num_groups)
    if shape.kind == "prefill":
        return cfg, shape, prefill_batch_struct(cfg, shape)
    return cfg, shape, decode_input_struct(cfg, shape)


# ---------------------------------------------------------------------------
# abstract params / optimizer state

def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(model_lib.init, cfg=cfg),
        # repro: ignore[RV102] eval_shape only traces — the key's value is never consumed
        jax.random.key(0))


def abstract_opt_state(optimizer, params_struct):
    return jax.eval_shape(optimizer.init, params_struct)


# ---------------------------------------------------------------------------
# steps

def make_group_grads(cfg: ModelConfig, *, microbatches: int = 1,
                     with_stats: bool = False):
    """``(params, batch) -> (losses (k,), stacked grads (k, *param))``.

    A sequential scan over the k batch-groups (gradient accumulation with
    per-group gradients kept separate): one group's activations live at a
    time, and shard_map regions (MoE EP) stay legal.  Each group is itself
    data-parallel over the full data axis.  ``with_stats`` (a config with
    ``cfg.deepseek_moe``, one microbatch) appends each group's router
    counters, stacked: ``{"expert_loads": (k, expert layers, held)}``."""
    if with_stats and microbatches != 1:
        raise NotImplementedError("router counters with microbatches")

    def group_value_and_grad(params, group_batch):
        if with_stats:
            (loss, stats), grads = jax.value_and_grad(
                model_lib.loss_and_stats, has_aux=True)(
                    params, group_batch, cfg)
            return loss, grads, stats
        if microbatches == 1:
            return jax.value_and_grad(model_lib.loss_fn)(
                params, group_batch, cfg)

        def reshape(x):
            n = x.shape[0]
            assert n % microbatches == 0
            return x.reshape((microbatches, n // microbatches) + x.shape[1:])

        mb = jax.tree.map(reshape, group_batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def mb_step(carry, b):
            g_acc, l_acc = carry
            l, g = jax.value_and_grad(model_lib.loss_fn)(params, b, cfg)
            g_acc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + l), None

        (g, l), _ = jax.lax.scan(
            mb_step, (zero, jnp.zeros((), jnp.float32)), mb)
        inv = 1.0 / microbatches
        return l * inv, jax.tree.map(lambda x: x * inv, g)

    def group_grads(params, batch):
        def group_step(_, group_batch):
            return None, group_value_and_grad(params, group_batch)

        _, out = jax.lax.scan(group_step, None, batch)
        return out

    return group_grads


def report_groups(grads, rc: RobustConfig, key, round_index):
    """The reports the server receives for stacked group gradients:
    ``rc.num_byzantine`` of the ``rc.num_workers`` groups, drawn from
    ``key``, replaced by ``rc.attack``.  Returns ``(reported, mask)``."""
    mask = byzantine.sample_byzantine_mask(
        key, rc.num_workers, rc.num_byzantine, rotate=rc.rotate_byzantine,
        round_index=round_index)
    attack = byzantine.get_attack(rc.attack)
    return attack(grads, mask, key, **dict(rc.attack_kwargs)), mask


def group_grad_layout(params, mesh, cfg: ModelConfig, *,
                      gather_grads: bool = False, fsdp: bool = True,
                      target_backend: str = "tpu"):
    """``(stacked grad shardings, ShardSpec)`` of the group step on ``mesh``.

    Shard-local: the stacked gradients keep the param layout, partitioned
    over ``model``.  ``gather_grads``: fully replicated, aggregated with a
    one-shard spec — the dense O(d)-per-device baseline."""
    spec = sharding.grad_shard_spec(mesh, cfg, target_backend=target_backend)
    if gather_grads:
        return (sharding.gathered_grad_shardings(params, mesh),
                dataclasses.replace(spec, num_shards=1))
    return (sharding.stacked_grad_shardings(params, mesh, cfg, fsdp=fsdp),
            spec)


def make_group_train_step(cfg: ModelConfig, rc: RobustConfig, optimizer, *,
                          microbatches: int = 1, grad_shardings=None,
                          schedule: byzantine.AttackSchedule | None = None,
                          shard_spec=None, remat_policy=None):
    """Group-mode robust train step (the production/dry-run path).

    rc.num_workers is interpreted as k (the number of batches); the attack
    mask has k entries with rc.num_byzantine contaminated batches.
    ``grad_shardings`` (optional pytree of NamedSharding for the stacked
    (k, *param) gradients) anchors the scan output so the cross-data
    gradient reduction lowers as reduce-scatter into the optimizer layout —
    and, crucially, keeps the gradients PARTITIONED over the model axis
    end-to-end: aggregation consumes the per-shard slices directly, no
    O(d) gather ever materializes (the shard-local contract,
    ``repro.core.shard_aggregation``).

    ``shard_spec`` (a ``ShardSpec``, usually
    ``launch.sharding.grad_shard_spec(mesh, cfg)``) reaches
    ``aggregate_reported`` so norm-based rules route their reductions
    through the blocked contract and ``round_backend`` auto-dispatch keys
    off the TARGET backend instead of the lowering host's — a dry-run sweep
    lowering TPU programs from a CPU host resolves the production path.

    Aggregation dispatches through ``robust_train.aggregate_reported`` —
    the same registry path the scenario engine uses — so ``rc.aggregator``
    (gmom / mean / trimmed_mean / krum / ...) and ``rc.round_backend`` (the
    fused Pallas round kernel vs the jnp reference) are first-class here,
    not pinned to the inline gmom pipeline this step used to hard-code.
    With ``rc.num_batches == k`` the gmom grouping is the identity (each
    batch-group mean is its own "batch"), reproducing the historical
    trim + Weiszfeld tail value for value.

    The step's layers run under ``jax.named_scope``s: ``group_fwd_bwd``,
    ``attack``, ``aggregate`` (inside it the rule's own: ``encode`` /
    ``decode``, ``batch_means``, ``trim``, ``weiszfeld``, ``round_kernel``),
    ``optimizer`` and ``step_metrics``; they name the device ops in a
    profile.  ``metrics["weiszfeld_iters"]`` (int32) is the reference
    Weiszfeld loop's step count, 0 under rules that do not run it.  A config
    whose router counts its assignments (``cfg.deepseek_moe``) adds
    ``moe_local_assignments`` (int32: assignments to the held experts, over
    the k groups and the expert layers) and ``moe_load_max`` (per layer the
    largest held expert's load over the held experts' mean, loads summed
    over the groups; the worst layer).

    ``schedule`` threads a multi-round ``AttackSchedule`` through the step
    (the pod-sweep path: attack × schedule at batch-mean granularity).
    When given, the step signature gains the adversary's carried state:
    ``train_step(params, opt_state, batch, key, round_index, attack_state)
    -> (params, opt_state, metrics, attack_state)``; without it the
    historical 5-arg signature is unchanged.

    ``remat_policy`` is the ``jax.checkpoint`` policy of the model's blocks
    under ``cfg.remat`` (None: full remat; ``jit_group_train_step`` chooses
    it from the device's memory).
    """
    router_stats = cfg.deepseek_moe
    group_grads = make_group_grads(cfg, microbatches=microbatches,
                                   with_stats=router_stats)

    def _step_core(params, opt_state, batch, key, round_index, attack_state):
        with jax.named_scope("group_fwd_bwd"), \
                blocks.remat_policy(remat_policy):
            losses, grads, *stats = group_grads(params, batch)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(grads,
                                                         grad_shardings)
        with jax.named_scope("attack"):
            if schedule is None:
                reported, mask = report_groups(grads, rc, key, round_index)
            else:
                reported, mask, attack_state = schedule.apply(
                    grads, key, round_index, attack_state)
        info = {}
        with jax.named_scope("aggregate"):
            agg = aggregate_reported(reported, rc, key=key,
                                     shard_spec=shard_spec, info=info)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(agg, opt_state, params)
            params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  params, updates)
        with jax.named_scope("step_metrics"):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(agg)))
            metrics = {"loss_mean": jnp.mean(losses),
                       "loss_median": jnp.median(losses),
                       "agg_grad_norm": gnorm,
                       "byz_count": jnp.sum(mask.astype(jnp.int32)),
                       "weiszfeld_iters": info.get(
                           "weiszfeld_iters", jnp.zeros((), jnp.int32))}
            if router_stats:
                loads = jnp.sum(stats[0]["expert_loads"], axis=0)
                mean = jnp.mean(loads.astype(jnp.float32), axis=-1)
                metrics["moe_local_assignments"] = jnp.sum(loads)
                metrics["moe_load_max"] = jnp.max(
                    jnp.max(loads, axis=-1) / jnp.maximum(mean, 1.0))
        return params, opt_state, metrics, attack_state

    if schedule is None:
        def train_step(params, opt_state, batch, key, round_index):
            params, opt_state, metrics, _ = _step_core(
                params, opt_state, batch, key, round_index, None)
            return params, opt_state, metrics
    else:
        def train_step(params, opt_state, batch, key, round_index,
                       attack_state):
            return _step_core(params, opt_state, batch, key, round_index,
                              attack_state)

    return train_step


# Share of the device's memory a step saving its dots leaves free: the
# compiled peak counts neither what the process holds beside the step (the
# next batch, the caller's own arrays) nor the allocator's fragmentation.
REMAT_MARGIN = 1 / 16


def device_bytes_limit(device) -> int | None:
    """The bytes a program may hold on ``device`` (its allocator's
    ``bytes_limit``), or None where the runtime reports none: the CPU, and
    the described devices a dry run lowers for."""
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def choose_remat_policy(compile_saving_dots, bytes_limit: int | None):
    """``blocks.SAVE_DOTS`` where ``compile_saving_dots()`` compiles and its
    peak memory stays under ``bytes_limit`` less ``REMAT_MARGIN`` of it;
    otherwise None, full remat.  A ``bytes_limit`` of None is full remat,
    with nothing compiled."""
    if bytes_limit is None:
        return None
    try:
        compiled = compile_saving_dots()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return None
    peak = compiled.memory_analysis().peak_memory_in_bytes
    return (blocks.SAVE_DOTS if peak <= (1 - REMAT_MARGIN) * bytes_limit
            else None)


def jit_group_train_step(cfg: ModelConfig, rc: RobustConfig, optimizer,
                         params, opt_state, batch, *, mesh=None,
                         gather_grads: bool = False, microbatches: int = 1,
                         schedule: byzantine.AttackSchedule | None = None,
                         fsdp: bool = True, target_backend: str = "tpu"):
    """The group step, jitted with params and optimizer state donated.

    ``mesh=None`` is one device and no shardings.  On a mesh the inputs take
    the production shardings of ``launch.sharding`` and the stacked
    gradients stay partitioned over ``model`` (the shard-local contract);
    ``gather_grads=True`` instead constrains them fully replicated and
    aggregates with a one-shard spec — the dense O(d)-per-device baseline.
    ``params``, ``opt_state`` and ``batch`` may be arrays or
    ShapeDtypeStructs: only their shapes are read.  ``target_backend`` keys
    the round-backend dispatch (a dry run lowers TPU programs on a CPU host).

    Under ``cfg.remat`` the blocks' checkpoint policy is chosen here: the
    step is compiled saving its dots (``blocks.SAVE_DOTS``) for a PRNG key
    of ``(2,) uint32`` and an ``int32`` round, and kept if it fits the
    device (``choose_remat_policy``), else jitted with full remat.  A caller
    that compiles the kept step for those arguments gets that same program
    back from the compile cache.

    Returns ``(jitted_step, in_shardings)``; ``in_shardings`` is None
    without a mesh, else ``(params, opt_state, batch, key, round[,
    attack_state])`` shardings.
    """
    kw = dict(microbatches=microbatches, schedule=schedule)
    jit_kw, in_shardings = {}, None
    if mesh is not None:
        pshard = sharding.param_shardings(params, mesh, cfg, fsdp=fsdp)
        oshard = sharding.opt_state_shardings(opt_state, params, mesh, cfg,
                                              fsdp=fsdp)
        bshard = sharding.batch_shardings(batch, mesh)
        gshard, spec = group_grad_layout(params, mesh, cfg,
                                         gather_grads=gather_grads,
                                         fsdp=fsdp,
                                         target_backend=target_backend)
        kw.update(grad_shardings=gshard, shard_spec=spec)
        rep = sharding.replicated(mesh)
        in_shardings = (pshard, oshard, bshard, rep, rep)
        if schedule is not None:
            in_shardings += (jax.tree.map(
                lambda _: rep, jax.eval_shape(schedule.init_state)),)
        jit_kw["in_shardings"] = in_shardings

    def jit_with(policy):
        step = make_group_train_step(cfg, rc, optimizer, remat_policy=policy,
                                     **kw)
        return jax.jit(step, donate_argnums=(0, 1), **jit_kw)

    if not cfg.remat:
        return jit_with(None), in_shardings
    saving_dots = jit_with(blocks.SAVE_DOTS)
    args = (params, opt_state, batch,
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.int32))
    if schedule is not None:
        args += (jax.eval_shape(schedule.init_state),)
    device = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    policy = choose_remat_policy(lambda: saving_dots.lower(*args).compile(),
                                 device_bytes_limit(device))
    return (saving_dots if policy is not None else jit_with(None),
            in_shardings)


def make_mean_train_step(cfg: ModelConfig, optimizer, *,
                         microbatches: int = 1):
    """Failure-free baseline (paper Algorithm 1 at production scale):
    identical pipeline with k=1, mean aggregation, no attack."""
    rc = RobustConfig(num_workers=1, num_byzantine=0, num_batches=1,
                      aggregator="mean", attack="none", trim_multiplier=None)
    return make_group_train_step(cfg, rc, optimizer,
                                 microbatches=microbatches)


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return model_lib.prefill(params, cfg, batch)
    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, state, tokens, positions):
        return model_lib.decode_step(params, cfg, state, tokens, positions)
    return serve_step
