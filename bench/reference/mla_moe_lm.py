"""Plain reference of the robust training step of a DeepSeek-V3-style LM:
latent attention, a leading dense layer, then layers of shared and routed
experts, of which this chip holds a share.

Written from the published description (DeepSeek-V2 §2.1, arXiv:2405.04434;
DeepSeek-V3 §2.1 and §4.2, arXiv:2412.19437), in float32 with every matrix
product at ``Precision.HIGHEST``, importing nothing of the program.  The
numerics and the server's round (attack, geometric median, AdamW) are
``dense_lm``'s:

* pre-norm layers: RMSNorm, multi-head latent attention, RMSNorm, then a
  SwiGLU (the ``first_k_dense_replace`` leading layers, ``intermediate_size``
  wide) or the expert layer;
* latent attention without query compression: per-head queries of
  ``qk_nope_head_dim + qk_rope_head_dim``; a latent of ``kv_lora_rank``
  (RMSNorm, eps 1e-6) and one rotary key of ``qk_rope_head_dim`` shared by
  every head, both from one projection of the input; per-head keys and
  values of ``v_head_dim`` from the latent; rotary embeddings on the rotary
  dims only (rotate-half); causal softmax at scale
  ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``;
* the expert layer: sigmoid scores over all ``n_routed_experts_published``
  experts; the top ``num_experts_per_tok`` of score + selection bias are
  chosen, weighted by their scores normalised over the chosen and times
  ``routed_scaling_factor``; the ``n_routed_experts`` held here, from
  ``experts_held_from`` (0 where the key is absent) on, give their weighted
  SwiGLU, the others nothing; the
  ``n_shared_experts`` shared experts, one SwiGLU of ``n_shared_experts *
  moe_intermediate_size``, run on every token;
* the sequence-wise balance loss, alpha times the sum over experts of f_i
  P_i for each sequence, averaged over the sequences: f_i the share of the
  sequence's top-k picks of the plain scores that go to expert i, times
  E / K, and P_i the mean over the sequence of expert i's score normalised
  over all experts;
* a final RMSNorm, the output head and the mean next-token cross entropy.

Each held expert is computed on every token and weighted by 0 where it was
not chosen: the same result as sending it only its tokens, with nothing
dropped.  The reference walks the layers, the heads and the experts one at
a time, rematerialising each, so that it fits on one chip once the
program's state is freed.  ``precision="fp8"`` is ``dense_lm``'s control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import (F32, _adamw_leaf, _mm, _norm2,
                                      _rmsnorm, _rope, byzantine_mask,
                                      geometric_median)

LATENT_NORM_EPS = 1e-6


def _swiglu(x, p, mm):
    gate = mm("btd,df->btf", x, p["w_gate"].astype(F32))
    up = mm("btd,df->btf", x, p["w_up"].astype(F32))
    return mm("btf,fd->btd", jax.nn.silu(gate) * up, p["w_down"].astype(F32))


def _latent_attention(x, a, cfg, mm):
    """x (B, T, D) -> (B, T, D)."""
    b, t, _ = x.shape
    h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, r = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    theta = cfg["rope_theta"]
    q = mm("btd,dhk->bthk", x, a["wq"].astype(F32))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv_a = mm("btd,dr->btr", x, a["wkv_a"].astype(F32))
    latent = _rmsnorm(kv_a[..., :r], a["kv_norm"]["scale"], LATENT_NORM_EPS)
    k_rope = _rope(kv_a[:, :, None, r:], theta)                # (B,T,1,rope)
    kv = mm("btr,rhk->bthk", latent, a["wkv_b"].astype(F32))
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]  # (Tq, Tk)
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def one(args):                  # one (row, head): a (T, T) score block
        qh, kh, vh = args
        s = jnp.where(causal, mm("tk,sk->ts", qh, kh) * scale, -1e30)
        return mm("ts,sv->tv", jax.nn.softmax(s, axis=-1), vh)

    heads = lambda z: z.transpose(0, 2, 1, 3).reshape(b * h, t, -1)  # noqa
    out = jax.lax.map(one, (heads(q), heads(k), heads(v)))     # (B*H, T, v)
    out = out.reshape(b, h, t, -1).transpose(0, 2, 1, 3).reshape(b, t, -1)
    return mm("btf,fd->btd", out, a["wo"].astype(F32))


def route(x, m, cfg, mm):
    """The router on (B, T, D): ``(ids (B, T, K) of score + bias, weights
    (B, T, E) zero off the chosen, balance loss)``."""
    e = cfg["n_routed_experts_published"]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm("btd,de->bte", x, m["router"].astype(F32)))
    _, ids = jax.lax.top_k(scores + m["router_bias"].astype(F32), k)
    chosen = jnp.sum(jax.nn.one_hot(ids, e, dtype=F32), axis=-2)
    g = scores * chosen
    g = g / jnp.sum(g, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    _, plain = jax.lax.top_k(scores, k)
    f = jnp.mean(jnp.sum(jax.nn.one_hot(plain, e, dtype=F32), axis=-2),
                 axis=1) * (e / k)                              # (B, E)
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    balance = cfg["balance_alpha"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return ids, g, balance


def _experts(x, m, cfg, mm):
    """x (B, T, D) -> (routed + shared (B, T, D), balance loss)."""
    _, g, balance = route(x, m, cfg, mm)
    lo = cfg.get("experts_held_from", 0)
    g = g[..., lo:lo + cfg["n_routed_experts"]]

    @jax.checkpoint
    def one(args):                  # one held expert over every token
        w, gate_w, up_w, down_w = args
        y = _swiglu(x, {"w_gate": gate_w, "w_up": up_w, "w_down": down_w},
                    mm)
        return w[..., None] * y

    ex = m["experts"]
    routed = jnp.sum(jax.lax.map(one, (
        jnp.moveaxis(g, -1, 0), ex["w_gate"], ex["w_up"],
        ex["w_down"])), axis=0)
    return routed + _swiglu(x, m["shared"], mm), balance


def _layer(x, p, cfg, mm):
    eps = cfg["rms_norm_eps"]
    x = x + _latent_attention(_rmsnorm(x, p["ln_attn"]["scale"], eps),
                              p["attn"], cfg, mm)
    h = _rmsnorm(x, p["ln_mlp"]["scale"], eps)
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"], mm), jnp.zeros((), F32)
    out, balance = _experts(h, p["moe"], cfg, mm)
    return x + out, balance


def _stack(params):
    """The layers in order, each a pytree of one layer's weights."""
    out = []
    for name in ("dense_layers", "layers"):
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            out += [jax.tree.map(lambda l: l[i], params[name])
                    for i in range(n)]
    return out


def group_loss(params, tokens, labels, cfg, *, precision="f32",
               chunk: int = 512):
    """Mean next-token cross entropy over every position of (B, T), plus
    the expert layers' balance losses."""
    mm = _mm(precision)
    x = params["embed"][tokens].astype(F32)
    layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, mm=mm))
    aux = jnp.zeros((), F32)
    for p in _stack(params):
        x, balance = layer(x, p)
        aux = aux + balance
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

    return jnp.sum(jax.lax.map(nll, (xs, ys))) / (b * t) + aux


@functools.partial(jax.jit, static_argnames=("cfg",))
def routing(params, tokens, cfg):
    """Each expert layer's choice for (B, T) tokens in the f32 forward:
    ``(layers, B, T, K)`` expert ids."""
    cfg = dict(cfg)
    mm = _mm("f32")
    x = params["embed"][tokens].astype(F32)
    out = []
    for p in _stack(params):
        if "moe" in p:
            h = _latent_attention(
                _rmsnorm(x, p["ln_attn"]["scale"], cfg["rms_norm_eps"]),
                p["attn"], cfg, mm)
            out.append(route(_rmsnorm(x + h, p["ln_mlp"]["scale"],
                                      cfg["rms_norm_eps"]),
                             p["moe"], cfg, mm)[0])
        x = _layer(x, p, cfg, mm)[0]
    return jnp.stack(out)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _group_value_and_grad(params, tokens, labels, cfg, precision):
    return jax.value_and_grad(group_loss)(params, tokens, labels,
                                          dict(cfg), precision=precision)


def train_steps(params, batches, keys, cfg: dict, robust: dict, adam: dict,
                *, precision: str = "f32", half_batch: bool = False):
    """``dense_lm.train_steps`` with this model's group loss: the robust
    step ``len(batches)`` times from ``params``; returns the final
    parameters, each step's mean group loss and the per-leaf norms of step
    0's aggregate (flattening order)."""
    cfg_t = tuple(sorted((k, v) for k, v in cfg.items()
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
