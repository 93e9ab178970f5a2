"""Plain float32 dense decoder: the yardstick that decides ``correct``.

It imports nothing of the program.  It makes its weights from the seed by
the program's recipe (``jax.random.split`` of ``PRNGKey(seed)`` into one
key per layer, the embedding and the head; normals scaled by one over the
square root of the input width), one layer at a time on one device, so
that it fits beside nothing else after the program's state is freed.  The
program's init leaves norm scales at one and biases at zero, where a
dropped bias or scale would read the same; so the harness draws them
from the seed (``free_params``) and writes the same draws into the
program's parameters before the window.  Matmuls run at ``HIGHEST`` precision, which is
float32 on a TPU.

Features: QKV bias, GQA or MHA, tied or untied output head, rotate-half
RoPE, RMSNorm, SwiGLU.

``control=True`` also runs the same forward with every matmul operand
rounded to float8 e4m3 (activations scaled per row, weights per tensor):
the precision below the program's bfloat16, which ``correct`` has to
refuse.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from bench.counts import sizes

F8_MAX = 448.0


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# --------------------------------------------------------------------------- #
# weights, by the program's recipe
# --------------------------------------------------------------------------- #
def _normal(key, shape, in_dim):
    jax, jnp = _jax()
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(in_dim))


def free_params(key, s: dict, qkv_bias: bool) -> dict:
    """Norm scales (uniform in [0.5, 1.5]) and QKV biases (normal, scale
    0.5), stacked over layers.  ``key`` is ``PRNGKey(seed)``; the draws
    come from a stream of it that the program's init does not use."""
    jax, jnp = _jax()
    keys = jax.random.split(jax.random.fold_in(key, 0x6E6F726D), 6)
    n, d, hd = s["layers"], s["d"], s["head_dim"]

    def scale(k, shape):
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)

    out = {"ln1": scale(keys[0], (n, d)), "ln2": scale(keys[1], (n, d)),
           "ln_f": scale(keys[2], (d,))}
    if qkv_bias:
        for k, name, h in zip(keys[3:], ("bq", "bk", "bv"),
                              (s["heads"], s["kv_heads"], s["kv_heads"])):
            out[name] = 0.5 * jax.random.normal(k, (n, h * hd), jnp.float32)
    return out


def layer_weights(key, s: dict, free: dict) -> dict:
    """Layer weights from its key; ``free``: its slice of ``free_params``."""
    jax, jnp = _jax()
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    d, hd, h, kv, ff = s["d"], s["head_dim"], s["heads"], s["kv_heads"], s["ff"]
    w = {
        "wq": _normal(ka[0], (d, h * hd), d),
        "wk": _normal(ka[1], (d, kv * hd), d),
        "wv": _normal(ka[2], (d, kv * hd), d),
        "wo": _normal(ka[3], (h * hd, d), h * hd),
        "w_up": _normal(km[0], (d, ff), d),
        "w_down": _normal(km[1], (ff, d), ff),
        "w_gate": _normal(km[2], (d, ff), d),
    }
    w.update({k: v for k, v in free.items() if k != "ln_f"})
    return w


def outer_weights(keys, s: dict, tied: bool) -> dict:
    """Embedding ``[V, D]`` and head ``[D, V]``."""
    embed = _normal(keys[-2], (s["vocab"], s["d"]), s["vocab"])
    head = embed.T if tied else _normal(keys[-1], (s["d"], s["vocab"]), s["d"])
    return {"embed": embed, "head": head}


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _q8(x, axis):
    """Round to float8 e4m3 with a scale per ``axis`` slice (None: one
    scale for the tensor), back in float32."""
    jax, jnp = _jax()
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, low: bool):
    jax, jnp = _jax()
    if low:
        a, b = _q8(a, -1), _q8(b, None)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    jax, jnp = _jax()
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    jax, jnp = _jax()
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _layer(x, w, cos, sin, *, s, eps, low):
    """One decoder layer over ``x [N, T, D]`` (causal)."""
    jax, jnp = _jax()
    n, t, d = x.shape
    hd, h, kv = s["head_dim"], s["heads"], s["kv_heads"]
    y = _rms(x, w["ln1"], eps)
    q = _mm("ntd,df->ntf", y, w["wq"], low)
    k = _mm("ntd,df->ntf", y, w["wk"], low)
    v = _mm("ntd,df->ntf", y, w["wv"], low)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(n, t, h, hd), cos, sin)
    k = _rope(k.reshape(n, t, kv, hd), cos, sin)
    v = v.reshape(n, t, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    if low:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, None)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if low:
        p = _q8(p, -1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(n, t, h * hd)
    x = x + _mm("ntf,fd->ntd", o, w["wo"], low)
    y = _rms(x, w["ln2"], eps)
    g = jax.nn.silu(_mm("ntd,df->ntf", y, w["w_gate"], low))
    u = _mm("ntd,df->ntf", y, w["w_up"], low)
    return x + _mm("ntf,fd->ntd", g * u, w["w_down"], low)


def _head_stats(x, head, ln_f, targets, *, eps, low):
    """Per position of one sequence ``x [T, D]``: the best logit, the
    logit of ``targets``, and the token the head puts first."""
    jax, jnp = _jax()
    logits = _mm("td,dv->tv", _rms(x, ln_f, eps), head, low)
    return (logits.max(-1),
            jnp.take_along_axis(logits, targets[:, None], -1)[:, 0],
            logits.argmax(-1).astype(jnp.int32))


@functools.lru_cache(maxsize=None)
def _programs(s_items: tuple, eps: float, theta: float, qkv_bias: bool,
              tied: bool, t: int):
    jax, jnp = _jax()
    s = dict(s_items)
    inv = 1.0 / (theta ** (np.arange(0, s["head_dim"], 2, dtype=np.float64)
                           / s["head_dim"]))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    layer = {low: jax.jit(functools.partial(
        lambda x, w, low: _layer(x, w, cos, sin, s=s, eps=eps, low=low),
        low=low)) for low in (False, True)}
    head = {low: jax.jit(functools.partial(_head_stats, eps=eps, low=low))
            for low in (False, True)}
    return {
        "layer": layer, "head": head,
        "free": jax.jit(lambda key: free_params(key, s, qkv_bias)),
        "weights": jax.jit(lambda key, free: layer_weights(key, s, free)),
        "outer": jax.jit(lambda keys: outer_weights(keys, s, tied)),
        "embed": jax.jit(lambda table, toks: table[toks]),
    }


def gaps(config: dict, param_seed: int, seqs: list[tuple[list, list]],
         t: int, rows: int, control: bool = False) -> dict:
    """Logit gaps over ``seqs``, each ``(prompt, served tokens)``.

    At every position where a token was served, the gap is the reference's
    best logit less the reference's logit of that token: 0 where the
    program served the reference's own greedy token, small where bfloat16
    rounding reordered a near tie.  ``t`` is the padded length (causal, so
    the padding changes nothing before it).

    Returns per-sequence arrays of the served gaps, and with ``control``
    the gaps of the tokens that the float8 forward puts first at the same
    positions.
    """
    jax, jnp = _jax()
    s = sizes(config)
    eps = float(config["rms_norm_eps"])
    tied = bool(config["tie_word_embeddings"])
    progs = _programs(tuple(sorted(s.items())), eps, float(config["rope_theta"]),
                      bool(config.get("qkv_bias", False)), tied, t)
    if len(seqs) > rows:
        raise ValueError(f"{len(seqs)} sequences > {rows} rows")
    toks = np.zeros((rows, t), np.int32)
    targets = np.zeros((rows, t), np.int32)
    spans = []
    for i, (prompt, served) in enumerate(seqs):
        full = list(prompt) + list(served)
        if len(full) - 1 > t:
            raise ValueError(f"sequence of {len(full)} tokens > {t}")
        toks[i, :len(full) - 1] = full[:-1]
        targets[i, :len(full) - 1] = full[1:]
        spans.append((len(prompt) - 1, len(full) - 1))
    root = jax.random.PRNGKey(param_seed)
    keys = jax.random.split(root, s["layers"] + 2)
    outer = progs["outer"](keys)
    free = progs["free"](root)
    x = progs["embed"](outer["embed"], jnp.asarray(toks))
    xs = {False: x, True: x} if control else {False: x}
    for i in range(s["layers"]):
        w = progs["weights"](keys[i], {k: v[i] for k, v in free.items()
                                       if k != "ln_f"})
        xs = {low: progs["layer"][low](xl, w) for low, xl in xs.items()}
        del w
    ln_f = free["ln_f"]
    out = {"served": [], "control": []}
    for i, (a, b) in enumerate(spans):
        head = progs["head"][False]
        best, at, _ = head(xs[False][i], outer["head"], ln_f,
                           jnp.asarray(targets[i]))
        out["served"].append(np.asarray(best - at)[a:b])
        if control:
            _, _, first = progs["head"][True](xs[True][i], outer["head"],
                                              ln_f, jnp.asarray(targets[i]))
            _, at_first, _ = head(xs[False][i], outer["head"], ln_f, first)
            out["control"].append(np.asarray(best - at_first)[a:b])
    return out
