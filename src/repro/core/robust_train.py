"""Byzantine-robust distributed training step.

This is the paper's Algorithm 2 realized as a single jit/pjit-able SPMD
function:

    per-worker grads (vmap over the worker-sharded batch axis)
      -> simulated Byzantine corruption of reported gradients
      -> robust aggregation (GMoM by default)
      -> optimizer update

The worker axis is the mesh ``data`` axis (x ``pod`` on multi-pod meshes):
worker j's shard of the global batch is the paper's S_j, and GSPMD keeps
worker j's gradient on data-rank j because the stacked gradient's leading
axis is sharded over ``data``.

The same function covers the failure-free baseline (attack="none",
aggregator="mean" == paper Algorithm 1) so baseline and robust runs share
every other line of code.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import aggregators, byzantine

# repro: train-scan — the multi-round scan carry below is the bit-exact
# resume surface: every carry element must be a TrainState field (PR 2
# checkpoint contract, repro.verify RV106).


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Static configuration of the robust aggregation pipeline."""
    num_workers: int
    num_byzantine: int = 0
    num_batches: int | None = None      # None => paper's canonical choice
    aggregator: str = "gmom"
    attack: str = "none"
    attack_kwargs: tuple = ()           # tuple of (key, value) — hashable
    rotate_byzantine: bool = True
    epsilon: float = 0.1                # the paper's fixed eps in 2(1+eps)q<=k
    trim_multiplier: float | None = 3.0
    gmom_max_iters: int = 32
    gmom_tol: float = 1e-7
    grouping_scheme: str = "contiguous"
    # gmom hot-path lowering: "auto" (fused Pallas round kernel on TPU,
    # jnp reference elsewhere), "fused", "fused_interpret", or "reference".
    # The golden traces are recorded on the reference path.
    round_backend: str = "auto"
    # wire format of the worker -> server reports (repro.core.compression):
    # "none" (full precision), "sign" (1 bit/coordinate), or
    # "int8_stochastic".  The server decodes before aggregation unless the
    # aggregator's registered native_codec matches, in which case the rule
    # consumes the payload directly (sign_sgd_majority votes on packed
    # sign bits without ever reconstructing float gradients).
    compression: str = "none"
    # arrival model (repro.core.staleness): which workers deliver a fresh
    # report each round.  "all_sync" with staleness_bound=0 is the paper's
    # synchronous regime and compiles to the identical HLO (empty buffer
    # carry).  Any other setting threads a bounded-staleness buffer through
    # the scan: fresh reports merge with <=tau-stale buffered ones, rows
    # are discount**age-weighted, and age > tau rows are hard-dropped.
    # Semantics: docs/ASYNC.md.
    arrival: str = "all_sync"
    staleness_bound: int = 0
    staleness_discount: float = 0.7
    arrival_kwargs: tuple = ()          # tuple of (key, value) — hashable

    def resolved_num_batches(self) -> int:
        if self.num_batches is not None:
            return self.num_batches
        from repro.core.grouping import choose_num_batches
        return choose_num_batches(self.num_workers, self.num_byzantine,
                                  epsilon=self.epsilon)


def per_worker_grads(loss_fn: Callable, params, worker_batches, *,
                     loss_kwargs: dict | None = None):
    """Stacked gradients: leaf shapes (m, *param_shape).

    ``worker_batches`` is a pytree whose leaves have leading dim m (the worker
    axis).  vmap over that axis computes each worker's gradient from its own
    shard only — the SPMD realization of "machine j computes grad f̄^(j)".

    Returns (stacked_grads, per_worker_loss).
    """
    loss_kwargs = loss_kwargs or {}

    def one_worker(batch):
        return jax.value_and_grad(loss_fn)(params, batch, **loss_kwargs)

    losses, grads = jax.vmap(one_worker, in_axes=(0,))(worker_batches)
    return grads, losses


def aggregate_reported(reported_grads, cfg: RobustConfig, *, key,
                       shard_spec=None, staleness=None, info=None):
    """Robust aggregation of already-(possibly-)corrupted reports.

    Which config fields an aggregator receives is driven by its registry
    metadata (the ``needs_*`` flags on ``aggregators.register``), not by a
    hardcoded name list: a newly registered rule declares what it consumes
    and gets it threaded here without touching this dispatch site.  Rules
    take ``**_kw`` so a bundle field they don't consume is swallowed.

    ``shard_spec`` (a :class:`repro.core.shard_aggregation.ShardSpec`)
    describes how the stacked gradients are partitioned over param shards;
    it reaches every rule that registered ``needs_shard_spec`` (the
    norm-based rules whose reductions cross shards — coordinate-wise rules
    are shard-local without it).

    ``cfg.compression`` selects the wire format (repro.core.compression):
    reports are encoded worker-side, and the server decodes the payload
    back to a float pytree before aggregation — unless the aggregator's
    registered ``native_codec`` matches the configured codec, in which
    case the payload is passed straight through (with the original tree as
    the ``like=`` shape/dtype template) and the rule consumes the wire
    format directly.

    ``staleness`` is an ``(age, bound, discount)`` triple from the
    bounded-staleness buffer (repro.core.staleness): rows are rescaled by
    their normalized ``discount**age`` weights (exactly 1.0 when fresh,
    exactly 0.0 past the bound) BEFORE the wire codec sees them — the
    server weighs what it has, then encodes/aggregates as usual.

    ``info`` (a dict) is handed to the rule, which may record what it did
    in it: the geometric-median rules store their Weiszfeld step count
    under ``"weiszfeld_iters"``.  It is a side channel for the step's
    metrics; nothing in it reaches the aggregate.

    This function is the Layer C trust boundary: ``reported_grads`` is
    ``report``-tainted (adversary-controlled end to end, including any
    wire payloads and codec scales derived from it downstream), and the
    RV301 invariant is that its influence exits this call only through
    the aggregator's declared sanitization point — nothing here may mix a
    report-derived value into the output after the rule runs (see
    repro.verify.taint and docs/STATIC_ANALYSIS.md).
    """
    agg = aggregators.get_aggregator(cfg.aggregator)
    kwargs: dict[str, Any] = {}
    if staleness is not None:
        from repro.core import staleness as staleness_lib
        age, bound, discount = staleness
        reported_grads = staleness_lib.apply_staleness(
            reported_grads, age, bound, discount=discount)
    if cfg.compression != "none":
        from repro.core import compression
        codec = compression.get_codec(cfg.compression)
        ckey = None
        if codec.needs_key:
            if key is None:
                raise ValueError(
                    f"compression {cfg.compression!r} needs a PRNG key")
            ckey = jax.random.fold_in(key, 29)
        with jax.named_scope("encode"):
            payload = codec.encode(reported_grads, key=ckey,
                                   shard_spec=shard_spec)
        if agg.native_codec == cfg.compression:
            kwargs.update(like=reported_grads)
            reported_grads = payload
        else:
            with jax.named_scope("decode"):
                reported_grads = codec.decode(payload, reported_grads)
    if agg.needs_num_byzantine:
        kwargs.update(num_byzantine=cfg.num_byzantine)
    if agg.needs_key:
        # NOTE: the paper's adversary sees the server's random bits — and so
        # do our omniscient attacks (they receive the same ``key``): the
        # attacker can adapt, which is exactly the §6 caveat under test.
        kwargs.update(key=jax.random.fold_in(key, 13))
    if agg.needs_grouping:
        kwargs.update(num_batches=cfg.resolved_num_batches(),
                      epsilon=cfg.epsilon,
                      grouping_scheme=cfg.grouping_scheme,
                      trim_multiplier=cfg.trim_multiplier,
                      max_iters=cfg.gmom_max_iters, tol=cfg.gmom_tol,
                      round_backend=cfg.round_backend)
    if agg.needs_shard_spec and shard_spec is not None:
        kwargs.update(shard_spec=shard_spec)
    if info is not None:
        kwargs.update(info=info)
    return agg(reported_grads, **kwargs)


def aggregate(stacked_grads, cfg: RobustConfig, *, key, round_index,
              shard_spec=None):
    """Attack simulation + robust aggregation.  Pure; jit-friendly."""
    mask = byzantine.sample_byzantine_mask(
        key, cfg.num_workers, cfg.num_byzantine,
        rotate=cfg.rotate_byzantine, round_index=round_index)
    attack = byzantine.get_attack(cfg.attack)
    attack_kwargs = dict(cfg.attack_kwargs)
    reported = attack(stacked_grads, mask, key, **attack_kwargs)
    return aggregate_reported(reported, cfg, key=key, shard_spec=shard_spec)


def make_robust_train_step(loss_fn: Callable, optimizer, cfg: RobustConfig, *,
                           loss_kwargs: dict | None = None,
                           donate: bool = False):
    """Build ``train_step(params, opt_state, worker_batches, key, round) ->
    (params, opt_state, metrics)``.

    ``optimizer`` follows the repro.optim interface: ``optimizer.update(
    grads, opt_state, params) -> (updates, opt_state)`` and params are
    updated by ``jax.tree.map(add)``.
    """

    def train_step(params, opt_state, worker_batches, key, round_index):
        stacked, losses = per_worker_grads(loss_fn, params, worker_batches,
                                           loss_kwargs=loss_kwargs)
        agg_grad = aggregate(stacked, cfg, key=key, round_index=round_index)
        updates, opt_state = optimizer.update(agg_grad, opt_state, params)
        params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                              params, updates)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(agg_grad)))
        metrics = {
            "loss_mean": jnp.mean(losses),
            # honest loss: mean over the workers that were *not* byzantine is
            # unknowable inside the step (mask is resampled) — report median
            # as a robust summary instead.
            "loss_median": jnp.median(losses),
            "agg_grad_norm": gnorm,
        }
        return params, opt_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# scan-compiled multi-round training (the adversarial scenario substrate)

def schedule_from_config(cfg: RobustConfig) -> byzantine.AttackSchedule:
    """The AttackSchedule equivalent of the per-round ``aggregate`` path:
    rotating (or static) Byzantine set, fixed attack — so the scan runner
    reproduces the Python-loop trainer exactly."""
    name = "rotating" if cfg.rotate_byzantine else "static"
    return byzantine.make_schedule(
        name, num_workers=cfg.num_workers, num_byzantine=cfg.num_byzantine,
        attack=cfg.attack, attack_kwargs=cfg.attack_kwargs)


def make_run_rounds(loss_fn: Callable, optimizer, cfg: RobustConfig, *,
                    schedule: byzantine.AttackSchedule | None = None,
                    loss_kwargs: dict | None = None,
                    extra_metrics: Callable | None = None,
                    arrival=None):
    """Build a ``lax.scan``-compiled N-round trainer.

    Returns ``run(params, opt_state, worker_batches, key, *, num_rounds,
    start_round=0, attack_state=None, stale_buffer=None,
    per_round_batches=False) ->
    (params, opt_state, attack_state, stale_buffer, metrics)`` where
    ``metrics`` leaves are stacked over rounds.  All N rounds trace into ONE
    jitted scan whose carry is (params, opt_state, attack_state,
    stale_buffer) — a 50-round CPU scenario runs in seconds instead of N
    dispatches of a per-step jit.

    Round ``t`` uses ``jax.random.fold_in(key, t)`` as its step key, so the
    scan reproduces a Python loop over ``make_robust_train_step`` driven with
    the same per-round keys, step for step.

    With ``cfg.aggregator == "gmom"`` the per-round hot path (batch means ->
    Remark-2 trim -> Weiszfeld) dispatches through ``cfg.round_backend``: on
    TPU it is the fused Pallas round kernel
    (``repro.kernels.geomed.round.round_aggregate_kernel``) that keeps the
    whole pipeline VMEM-resident inside the scan body; elsewhere the
    golden-trace-stable jnp reference pipeline runs.

    * fixed-batch mode (default): ``worker_batches`` is the paper's full
      local data S_j, reused every round (Algorithm 1/2 exactly);
    * ``per_round_batches=True``: leaves carry a leading num_rounds axis and
      round t consumes slice t (the LM/streaming regime).

    ``schedule`` defaults to the RobustConfig-equivalent rotating/static
    schedule; pass any ``byzantine.AttackSchedule`` for multi-round
    adversaries (ramp-up, coordinated-switch, stealth-then-strike, ...).
    ``attack_state`` lets chunked callers (checkpoint boundaries) carry the
    adversary's memory across calls — prefer driving the runner through
    ``repro.core.train_state.advance``, which threads the whole
    (params, opt_state, attack_state, round, key, history, stale_buffer)
    TrainState and is what save/restore_train_state checkpoint.
    ``extra_metrics(params, agg_grad)`` appends scenario-specific metrics
    (e.g. estimation error vs true θ).

    ``arrival`` (a :class:`repro.core.staleness.ArrivalSchedule`, default
    ``staleness.arrival_from_config(cfg)``) turns on the bounded-staleness
    path: each round the arrival model picks the fresh reporters, stale
    workers contribute their buffered last report (age-discounted, dropped
    past τ), and the buffer joins the scan carry / ``stale_buffer``
    TrainState field.  When the arrival resolves to None (``all_sync``,
    τ=0) the carry slot is the empty pytree ``()`` and the compiled
    computation is unchanged — the synchronous path stays bit-identical.
    """
    schedule = schedule if schedule is not None else schedule_from_config(cfg)
    loss_kwargs = loss_kwargs or {}
    if arrival is None:
        from repro.core import staleness as staleness_lib
        arrival = staleness_lib.arrival_from_config(cfg)

    def _run(params, opt_state, worker_batches, key, attack_state,
             stale_buffer, num_rounds, start_round, per_round_batches):
        if attack_state is None:
            attack_state = schedule.init_state()
        if arrival is None:
            stale_buffer = ()
        elif stale_buffer is None:
            from repro.core import staleness as staleness_lib
            stale_buffer = staleness_lib.init_buffer(
                params, arrival.num_workers, arrival.staleness_bound)
        rounds = start_round + jnp.arange(num_rounds)

        def body(carry, xs):
            params, opt_state, astate, stale_buffer = carry
            if per_round_batches:
                t, batch = xs
            else:
                t, batch = xs, worker_batches
            key_t = jax.random.fold_in(key, t)
            with jax.named_scope("worker_grads"):
                stacked, losses = per_worker_grads(loss_fn, params, batch,
                                                   loss_kwargs=loss_kwargs)
            with jax.named_scope("attack"):
                reported, mask, astate = schedule.apply(stacked, key_t, t,
                                                        astate)
            with jax.named_scope("aggregate"):
                if arrival is None:
                    agg_grad = aggregate_reported(reported, cfg, key=key_t)
                else:
                    from repro.core import staleness as staleness_lib
                    fresh = arrival.arrive(key_t, t, mask)
                    reported, stale_buffer = staleness_lib.merge_reports(
                        stale_buffer, reported, fresh)
                    agg_grad = aggregate_reported(
                        reported, cfg, key=key_t,
                        staleness=(stale_buffer.age, stale_buffer.bound,
                                   cfg.staleness_discount))
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(agg_grad, opt_state,
                                                      params)
                params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                      params, updates)
            with jax.named_scope("step_metrics"):
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(agg_grad)))
                metrics = {
                    "loss_mean": jnp.mean(losses),
                    "loss_median": jnp.median(losses),
                    "agg_grad_norm": gnorm,
                    "byz_count": jnp.sum(mask.astype(jnp.int32)),
                }
                if arrival is not None:
                    metrics["stale_count"] = jnp.sum(
                        (stale_buffer.age > 0).astype(jnp.int32))
                if extra_metrics is not None:
                    metrics.update(extra_metrics(params, agg_grad))
            return (params, opt_state, astate, stale_buffer), metrics

        xs = (rounds, worker_batches) if per_round_batches else rounds
        carry, metrics = jax.lax.scan(
            body, (params, opt_state, attack_state, stale_buffer), xs)
        params, opt_state, attack_state, stale_buffer = carry
        return params, opt_state, attack_state, stale_buffer, metrics

    # start_round stays dynamic so chunked callers (checkpoint boundaries)
    # don't recompile per chunk.
    jitted = jax.jit(_run, static_argnames=("num_rounds",
                                            "per_round_batches"))

    def _args(params, opt_state, worker_batches, key, *, num_rounds=None,
              start_round=0, attack_state=None, stale_buffer=None,
              per_round_batches=False):
        if num_rounds is None:
            if not per_round_batches:
                raise ValueError("num_rounds is required with a fixed batch")
            num_rounds = jax.tree.leaves(worker_batches)[0].shape[0]
        if isinstance(stale_buffer, tuple) and stale_buffer == ():
            # the disabled-path TrainState default — _run re-derives it
            stale_buffer = None
        return (params, opt_state, worker_batches, key, attack_state,
                stale_buffer, num_rounds, start_round, per_round_batches)

    def run(*args, **kwargs):
        return jitted(*_args(*args, **kwargs))

    # ``run.lower(...)`` (same arguments) lowers the very program ``run``
    # dispatches, e.g. to read its compiled HLO
    run.lower = lambda *args, **kwargs: jitted.lower(*_args(*args, **kwargs))
    return run


def make_sharded_aggregate(cfg: RobustConfig, mesh=None, *,
                           axis: str = "model",
                           num_shards: int | None = None):
    """Shard-LOCAL aggregation body for code running inside ``shard_map``
    with the stacked gradients partitioned over ``axis`` (the ZeRO-1 layout:
    each device holds every worker's slice of its param shard).

    It covers EVERY registered rule over the *model* axis via the
    blocked-reduction contract (``repro.core.shard_aggregation``):
    coordinate-wise rules run with no collectives at all, norm-based rules
    all-reduce per-shard partial squared norms.  The result is
    bit-identical to the ``"virtual"``-mode single-device oracle on the
    gathered gradients — the testable form of "sharded and gathered
    aggregation agree exactly" (tests/test_shardmap_aggregate.py).

    Returns ``fn(stacked_local_grads, key) -> agg_grad_shard`` where each
    leaf of ``stacked_local_grads`` is the local LAST-dim slice (leading
    worker axis intact) and the returned aggregate is likewise the local
    shard.
    """
    if num_shards is None:
        if mesh is None:
            raise ValueError("make_sharded_aggregate needs a mesh or an "
                             "explicit num_shards")
        num_shards = mesh.shape[axis]
    from repro.core.shard_aggregation import ShardSpec
    spec = ShardSpec(num_shards=num_shards, mode="shard_map", axis=axis)

    def agg_local(stacked_local, key):
        return aggregate_reported(stacked_local, cfg, key=key,
                                  shard_spec=spec)

    return agg_local
