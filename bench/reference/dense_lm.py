"""Plain reference of the robust training step of a dense decoder LM.

Written from the published description, in float32 with every matrix
product at ``Precision.HIGHEST``, and importing nothing of the program:

* pre-norm decoder layers: RMSNorm, grouped-query attention with rotary
  position embeddings (rotate-half convention, theta from the config) and a
  causal mask limited to the config's ``sliding_window`` (a query at i sees
  keys i - window < j <= i), then RMSNorm and a SwiGLU MLP;
* a final RMSNorm, the output head and the mean next-token cross entropy
  over every position of a group;
* the server's round: each group's gradient is the report, in the
  parameters' dtype; ``byzantine`` of the groups, drawn from the step key,
  report ``-scale`` times it (sign flip); the aggregate is the plain mean
  or the geometric median of the reports (Weiszfeld from the trimmed mean,
  Remark-2 norm trimming at ``trim_multiplier`` times the median norm);
* AdamW with a linear warmup, moments in float32, parameters stored back in
  their own dtype.

Parameters are the pytree the benchmark made from its seed (``embed``,
``unembed``, ``ln_f``, and the stacked ``layers``).  ``precision="fp8"`` is
the lower-precision control: every matrix product as a program computing
in float8 would run it, its operands rounded to e4m3 (scaled per tensor by
the largest magnitude) and its result to bfloat16, the program's
activation dtype.

Memory: the reference holds the parameters, the float32 Adam moments and
the k reports, and walks the Weiszfeld iterate one leaf at a time, so that
it fits beside nothing else on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x):
    """x rounded to e4m3 under a per-tensor scale; the backward pass sees
    the rounding as the identity (straight through), so the gradients'
    products take the rounded operands."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(precision):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(
            spec, _fp8(a), _fp8(b), precision=HIGHEST).astype(
                jnp.bfloat16).astype(F32)
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x (B, T, H, hd), positions 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs          # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm, window):
    """Causal GQA within ``window`` (None: the whole prefix).
    q (B, T, H, hd), k/v (B, T, KV, hd) -> (B, T, H*hd).
    One (row, kv head) at a time, so that one (G, T, T) score block lives
    at once."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, t, kv, g, hd).transpose(0, 2, 1, 3, 4)  # (B,KV,T,G,hd)
    qg = qg.reshape(b * kv, t, g, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    vv = v.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    qi, kj = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    causal = kj <= qi                                          # (Tq, Tk)
    if window is not None:
        causal = causal & (kj > qi - window)

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args
        s = mm("tgh,sh->gts", qh, kh) * hd ** -0.5
        s = jnp.where(causal[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return mm("gts,sh->tgh", p, vh)

    out = jax.lax.map(one, (qg, kk, vv))                       # (B*KV,T,G,hd)
    out = out.reshape(b, kv, t, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, h * hd)


def _layer(x, p, cfg, mm):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    h = _rmsnorm(x, p["ln_attn"]["scale"], eps)
    q = _rope(mm("btd,dhk->bthk", h, a["wq"].astype(F32)), theta)
    k = _rope(mm("btd,dhk->bthk", h, a["wk"].astype(F32)), theta)
    v = mm("btd,dhk->bthk", h, a["wv"].astype(F32))
    att = _attention(q, k, v, mm, cfg.get("sliding_window"))
    x = x + mm("btf,fd->btd", att, a["wo"].astype(F32))
    m = p["mlp"]
    h = _rmsnorm(x, p["ln_mlp"]["scale"], eps)
    gate = mm("btd,df->btf", h, m["w_gate"].astype(F32))
    up = mm("btd,df->btf", h, m["w_up"].astype(F32))
    return x + mm("btf,fd->btd", jax.nn.silu(gate) * up,
                  m["w_down"].astype(F32))


def group_loss(params, tokens, labels, cfg, *, precision="f32",
               chunk: int = 512):
    """Mean next-token cross entropy over every position of (B, T)."""
    mm = _mm(precision)
    x = params["embed"][tokens].astype(F32)
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, mm=mm))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, jax.tree.map(lambda l: l[i], params["layers"]))
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg["rms_norm_eps"])
    b, t, d = x.shape
    chunk = min(chunk, t)
    xs = x.reshape(b, t // chunk, chunk, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(b, t // chunk, chunk).transpose(1, 0, 2)
    w = params["unembed"]

    @jax.checkpoint
    def nll(args):
        xc, yc = args
        logits = mm("bcd,dv->bcv", xc, w.astype(F32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        return jnp.sum(lse - picked)

    return jnp.sum(jax.lax.map(nll, (xs, ys))) / (b * t)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _group_value_and_grad(params, tokens, labels, cfg, precision):
    return jax.value_and_grad(group_loss)(params, tokens, labels,
                                          dict(cfg), precision=precision)


def byzantine_mask(key, round_index, groups: int, byzantine: int):
    """Which groups report falsely this step: a fresh uniformly random
    q-subset drawn from the step key folded with the round index (the
    lowest q of ``groups`` uniform scores, ties broken by index)."""
    if byzantine == 0:
        return np.zeros((groups,), bool)
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, round_index),
                                      (groups,)))
    rank = np.argsort(np.argsort(u, kind="stable"), kind="stable")
    return rank < byzantine


_sq = jax.jit(lambda a, b: jnp.sum(
    jnp.square(a.astype(F32) - b.astype(F32)), axis=tuple(range(1, a.ndim))))
_norm2 = jax.jit(lambda a: jnp.sum(jnp.square(a.astype(F32)),
                                   axis=tuple(range(1, a.ndim))))
# sum_j c_j z_j in f32, as one reduction over the stacked axis (no f32 copy
# of the stacked reports)
_wsum = jax.jit(lambda c, z: jnp.sum(
    c.reshape((-1,) + (1,) * (z.ndim - 1)) * z.astype(F32), axis=0))
_leaf_delta = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))


def geometric_median(reports: list, *, trim_multiplier, max_iters: int,
                     tol: float, eps: float = 1e-12) -> list:
    """Weiszfeld's geometric median of the k stacked reports, taken in the
    concatenation of all leaves; ``reports`` is a list of (k, ...) leaves.
    Returns float32 leaves."""
    k = reports[0].shape[0]
    if trim_multiplier is None:
        w = np.ones((k,), np.float64)
    else:
        norms = np.sqrt(sum(np.asarray(_norm2(z), np.float64)
                            for z in reports))
        w = (norms <= trim_multiplier * np.median(norms) + eps).astype(
            np.float64)
        if w.sum() == 0:
            w = np.ones_like(w)
    y = [_wsum(jnp.asarray(w / max(w.sum(), eps), F32), z) for z in reports]
    for _ in range(max_iters):
        sq = sum(np.asarray(_sq(z, yl[None]), np.float64)
                 for z, yl in zip(reports, y))
        inv = w / np.sqrt(sq + eps * eps)
        coef = jnp.asarray(inv / max(inv.sum(), eps), F32)
        delta = 0.0
        for i, z in enumerate(reports):
            new = _wsum(coef, z)
            delta += float(_leaf_delta(new, y[i]))
            y[i] = new
        if delta <= tol * tol:
            break
    return y


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw_leaf(p, mu, nu, g, lr, count, b1, b2, eps):
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mhat = mu / (1 - b1 ** count)
    vhat = nu / (1 - b2 ** count)
    p = (p.astype(F32) - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
    return p, mu, nu


def train_steps(params, batches, keys, cfg: dict, robust: dict, adam: dict,
                *, precision: str = "f32", half_batch: bool = False):
    """Run the robust step ``len(batches)`` times from ``params``.

    ``batches[i]`` is ``{"tokens", "labels"}`` of shape (k, B, T) and
    ``keys[i]`` step i's key; ``robust`` holds ``aggregator`` ("mean" or
    "gmom"), ``byzantine``, ``attack_scale``, ``trim_multiplier``,
    ``max_iters`` and ``tol``; ``adam`` holds ``peak_lr``,
    ``warmup_steps``, ``b1``, ``b2`` and ``eps``.  ``half_batch`` computes
    each group's loss over the first half of its positions only (a planted
    fault).  The parameters' buffers are donated to the updates.  Returns
    the final parameters, each step's mean group loss, and the norm of
    each leaf of step 0's aggregate (flattening order)."""
    cfg_t = tuple(sorted((k, v) for k, v in cfg.items()      # the sizes
                         if isinstance(v, (bool, int, float, type(None)))))
    leaves, treedef = jax.tree.flatten(params)
    mu = [jnp.zeros(l.shape, F32) for l in leaves]
    nu = [jnp.zeros(l.shape, F32) for l in leaves]
    losses, first_agg = [], None
    for step, (batch, key) in enumerate(zip(batches, keys)):
        params = jax.tree.unflatten(treedef, leaves)
        k = batch["tokens"].shape[0]
        mask = byzantine_mask(key, step, k, robust["byzantine"])
        reports, group_losses = [], []
        for g in range(k):
            tok, lab = batch["tokens"][g], batch["labels"][g]
            if half_batch:
                tok, lab = tok[:, :tok.shape[1] // 2], lab[:, :lab.shape[1] // 2]
            loss, grad = _group_value_and_grad(params, tok, lab, cfg_t,
                                               precision)
            group_losses.append(float(loss))
            grad = jax.tree.leaves(grad)
            if mask[g]:
                grad = [(-robust["attack_scale"] * x.astype(F32)).astype(x.dtype)
                        for x in grad]
            reports.append(grad)
            del grad
        del params
        losses.append(float(np.mean(group_losses)))
        stacked = []
        for i in range(len(leaves)):    # one leaf at a time, freeing it
            stacked.append(jnp.stack([r[i] for r in reports]))
            for r in reports:
                r[i] = None
        del reports
        dtypes = [z.dtype for z in stacked]
        if robust["aggregator"] == "mean":
            agg = [jnp.mean(z.astype(F32), axis=0) for z in stacked]
        elif robust["aggregator"] == "gmom":
            agg = geometric_median(
                stacked, trim_multiplier=robust["trim_multiplier"],
                max_iters=robust["max_iters"], tol=robust["tol"])
        else:
            raise ValueError(f"no reference for {robust['aggregator']!r}")
        del stacked
        norms = []
        lr = adam["peak_lr"] * min(1.0, (step + 1) / adam["warmup_steps"])
        for i in range(len(leaves)):    # the aggregate in the reports' dtype
            a = agg[i].astype(dtypes[i]).astype(F32)
            agg[i] = None
            norms.append(float(np.sqrt(np.sum(_norm2(a[None])))))
            leaves[i], mu[i], nu[i] = _adamw_leaf(
                leaves[i], mu[i], nu[i], a, F32(lr), F32(step + 1),
                F32(adam["b1"]), F32(adam["b2"]), F32(adam["eps"]))
            del a
        if first_agg is None:
            first_agg = norms
    return {"params": jax.tree.unflatten(treedef, leaves), "losses": losses,
            "first_agg_norms": first_agg}
