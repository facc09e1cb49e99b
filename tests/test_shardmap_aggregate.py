"""make_shardmap_aggregate (hand-scheduled GMoM collectives) vs the GSPMD
``aggregate`` path on a fake 8-device CPU mesh — leaf-for-leaf equality,
on both the reference jnp tail and the fused round-kernel backend
(``round_backend="fused_interpret"``: the Pallas kernel in interpret mode).

Runs in a subprocess because the virtual-device flag must be set before jax
initializes (same pattern as test_parallel_numerics)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import RobustConfig, aggregate, aggregators, \\
        make_shardmap_aggregate
    from repro.launch.mesh import make_mesh

    m, k = 8, 4
    mesh = make_mesh((8,), ("data",))
    cfg = RobustConfig(num_workers=m, num_byzantine=1, num_batches=k,
                       attack="none", aggregator="gmom",
                       gmom_max_iters=32, gmom_tol=1e-7)

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    stacked = {"w": jax.random.normal(ks[0], (m, 16), jnp.float32),
               "b": {"x": jax.random.normal(ks[1], (m, 4, 3), jnp.float32)}}

    # --- GSPMD path: plain aggregate() jitted with the worker axis sharded
    in_shardings = jax.tree.map(
        lambda x: NamedSharding(mesh, P(*(("data",) + (None,) * (x.ndim - 1)))),
        stacked)
    gspmd = jax.jit(
        lambda s: aggregate(s, cfg, key=key, round_index=0),
        in_shardings=(in_shardings,))(stacked)

    # --- hand-scheduled path: per-rank grads (no worker axis) via shard_map
    agg_local = make_shardmap_aggregate(cfg, mesh)
    specs = jax.tree.map(
        lambda x: P(*(("data",) + (None,) * (x.ndim - 1))), stacked)
    out_specs = jax.tree.map(lambda x: P(*((None,) * (x.ndim - 1))), stacked)
    fn = jax.shard_map(
        lambda s: agg_local(jax.tree.map(lambda x: x[0], s)),
        mesh=mesh, in_specs=(specs,), out_specs=out_specs, check_vma=False)
    handsched = jax.jit(fn)(stacked)

    # --- single-device oracle
    oracle = aggregators.gmom_aggregator(
        stacked, num_batches=k, num_byzantine=1,
        trim_multiplier=cfg.trim_multiplier, max_iters=cfg.gmom_max_iters,
        tol=cfg.gmom_tol)

    for a, b, c in zip(jax.tree.leaves(gspmd), jax.tree.leaves(handsched),
                       jax.tree.leaves(oracle)):
        assert a.shape == b.shape == c.shape, (a.shape, b.shape, c.shape)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5, "gspmd vs shard_map"
        assert float(jnp.max(jnp.abs(b - c))) < 1e-5, "shard_map vs oracle"

    # --- fused backend: the PR-3 round kernel dispatched through
    # RobustConfig.round_backend (the trim+Weiszfeld tail runs in the Pallas
    # interpreter on the psum'd means; identity k=m grouping in-kernel)
    import dataclasses
    cfg_fused = dataclasses.replace(cfg, round_backend="fused_interpret")
    agg_fused = make_shardmap_aggregate(cfg_fused, mesh)
    fn_fused = jax.shard_map(
        lambda s: agg_fused(jax.tree.map(lambda x: x[0], s)),
        mesh=mesh, in_specs=(specs,), out_specs=out_specs, check_vma=False)
    handsched_fused = jax.jit(fn_fused)(stacked)

    oracle_fused = aggregators.gmom_aggregator(
        stacked, num_batches=k, num_byzantine=1,
        trim_multiplier=cfg.trim_multiplier, max_iters=cfg.gmom_max_iters,
        tol=cfg.gmom_tol, round_backend="fused_interpret")

    for b, f, of in zip(jax.tree.leaves(handsched),
                        jax.tree.leaves(handsched_fused),
                        jax.tree.leaves(oracle_fused)):
        assert b.shape == f.shape == of.shape, (b.shape, f.shape, of.shape)
        assert float(jnp.max(jnp.abs(f - b))) < 1e-5, \\
            "fused shard_map vs reference shard_map"
        assert float(jnp.max(jnp.abs(f - of))) < 1e-5, \\
            "fused shard_map vs fused oracle"
    print("OK")
""")


def test_shardmap_gmom_matches_gspmd_aggregate():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout


# ---------------------------------------------------------------------------
# the shard-local contract: sharded (shard_map over the MODEL axis) vs
# gathered (the single-device "virtual" blocked oracle) aggregation must be
# BIT-identical for every registered rule × even/uneven grouping × dtype.

BLOCKED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import RobustConfig, aggregators, aggregate_reported, \\
        make_sharded_aggregate
    from repro.core.shard_aggregation import ShardSpec
    from repro.launch.mesh import make_mesh

    m, S = 8, 8
    mesh = make_mesh((S,), ("model",))
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    base = {"w": jax.random.normal(ks[0], (m, 16), jnp.float32),
            "b": {"x": jax.random.normal(ks[1], (m, 4, 8), jnp.float32)},
            "s": jax.random.normal(ks[2], (m,), jnp.float32)}

    def in_spec(x):
        if x.ndim == 1:
            return P(None)                       # (m,) — replicated
        return P(*((None,) * (x.ndim - 1) + ("model",)))

    def out_spec(x):
        if x.ndim == 0:
            return P()
        return P(*((None,) * (x.ndim - 1) + ("model",)))

    in_specs = jax.tree.map(in_spec, base)
    checked = 0
    for name in aggregators.available():
        # rules with a native wire codec run TWICE: once on raw floats and
        # once through their compressed production path (encode happens
        # inside aggregate_reported on both sides; the encode itself is
        # shard-local, so the bitwise contract must survive it)
        native = aggregators.get_aggregator(name).native_codec
        for codec in ("none",) + ((native,) if native else ()):
          for k in (4, 3):                        # even / uneven grouping
            for dt in (jnp.float32, jnp.bfloat16):
                stacked = jax.tree.map(lambda x: x.astype(dt), base)
                cfg = RobustConfig(
                    num_workers=m, num_byzantine=1, num_batches=k,
                    attack="none", aggregator=name, compression=codec,
                    gmom_max_iters=8, gmom_tol=1e-7)

                virtual = ShardSpec(num_shards=S, mode="virtual",
                                    axis="model")
                gathered = jax.jit(lambda s: aggregate_reported(
                    s, cfg, key=key, shard_spec=virtual))(stacked)

                agg = make_sharded_aggregate(cfg, mesh)
                out_specs = jax.tree.map(
                    out_spec, jax.eval_shape(
                        lambda s: aggregate_reported(s, cfg, key=key),
                        stacked))
                fn = jax.shard_map(agg, mesh=mesh,
                                   in_specs=(in_specs, P(None)),
                                   out_specs=out_specs, check_vma=False)
                sharded = jax.jit(fn)(stacked, key)

                for pa, b in zip(
                        jax.tree_util.tree_flatten_with_path(gathered)[0],
                        jax.tree.leaves(sharded)):
                    path, a = pa
                    assert a.shape == b.shape and a.dtype == b.dtype, \\
                        (name, codec, k, str(dt), str(path), a.shape, b.shape)
                    assert np.array_equal(np.asarray(a), np.asarray(b)), (
                        "sharded != gathered (bitwise)", name, codec, k,
                        str(dt), str(path),
                        float(np.max(np.abs(np.asarray(a, np.float64)
                                            - np.asarray(b, np.float64)))))
                checked += 1
    print("OK", checked)
""")


def test_every_aggregator_sharded_vs_gathered_bit_identical():
    """shard_map-mode aggregation on 8 model shards returns the same BITS
    as the gathered virtual-mode blocked oracle, for every registered
    aggregator × {even k=4, uneven k=3} grouping × {f32, bf16} — the
    testable form of the acceptance criterion "sharded and gathered
    aggregation are bit-identical for every registered rule"."""
    res = subprocess.run(
        [sys.executable, "-c", BLOCKED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout


# ---------------------------------------------------------------------------
# the Weiszfeld loop's collectives under the shard-local contract: the loop
# runs on the k×k Gram matrix, so the only cross-shard reduction of the
# geometric median is the one (k, k) partial-Gram combine before the loop.

GRAM_COLLECTIVES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import re
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import RobustConfig, make_sharded_aggregate
    from repro.launch.mesh import make_mesh
    from repro.roofline.hlo_parser import parse_computations

    m, k, S = 8, 4, 8
    mesh = make_mesh((S,), ("model",))
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    stacked = {"w": jax.random.normal(ks[0], (m, 16), jnp.float32),
               "b": jax.random.normal(ks[1], (m, 4, 8), jnp.float32)}
    cfg = RobustConfig(num_workers=m, num_byzantine=1, num_batches=k,
                       attack="none", aggregator="gmom",
                       gmom_max_iters=32, gmom_tol=1e-7)
    spec = lambda x: P(*((None,) * (x.ndim - 1) + ("model",)))
    fn = jax.shard_map(make_sharded_aggregate(cfg, mesh), mesh=mesh,
                       in_specs=(jax.tree.map(spec, stacked), P(None)),
                       out_specs={"w": P("model"), "b": P(None, "model")},
                       check_vma=False)
    text = jax.jit(fn).lower(stacked, jax.random.PRNGKey(0)).compile() \\
        .as_text()
    comps = parse_computations(text)
    COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute")

    def reach(name, seen):
        if name in seen or name not in comps:
            return seen
        seen.add(name)
        for ins in comps[name].instrs:
            for callee in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\\w.\\-]+)",
                    ins.rest):
                reach(callee, seen)
        return seen

    whiles = [ins for c in comps.values() for ins in c.instrs
              if ins.op == "while"]
    assert len(whiles) == 1, [w.name for w in whiles]
    inside = set()
    for callee in re.findall(r"(?:body|condition)=%?([\\w.\\-]+)",
                             whiles[0].rest):
        reach(callee, inside)
    assert inside
    colls = [(c.name, ins) for c in comps.values() for ins in c.instrs
             if ins.op.startswith(COLL)]
    in_loop = [ins.name for c, ins in colls if c in inside]
    assert in_loop == [], in_loop
    gram = [ins.name for _, ins in colls
            if f"f32[{S},{k},{k}]" in ins.result_text]
    assert len(gram) == 1, [ins.result_text for _, ins in colls]
    print("OK", len(colls))
""")


def test_blocked_gmom_has_one_gram_reduction_and_none_in_the_loop():
    """Under a blocked ShardSpec the compiled gmom aggregation holds no
    collective inside the Weiszfeld ``while`` and exactly one (k, k)
    partial-Gram all-gather before it (the trim's (k,) norms are the only
    other collective)."""
    res = subprocess.run(
        [sys.executable, "-c", GRAM_COLLECTIVES_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")))
    assert res.returncode == 0, (res.stdout[-800:], res.stderr[-4000:])
    assert "OK" in res.stdout
