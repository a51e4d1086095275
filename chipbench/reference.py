"""Plain float32 reference of the served models, independent of ``src/``.

It reads the ``program`` block of a configuration file (the sizes the program
is built with), makes the same random weights from the seed by the same key
derivation as the program's initialiser, and runs a straightforward forward
pass: embedding, per layer RMSNorm -> causal multi-head (grouped-query)
attention with RoPE -> residual -> RMSNorm -> SwiGLU FFN or a softmax-routed
top-k mixture of SwiGLU experts plus shared experts -> residual, final
RMSNorm and a tied LM head.  Matmuls run at precision ``highest``.

Layer equations follow the configuration's departures from the published
model (MHA for MLA, a tied head, renormalised top-k gates, the embedding
scaled by sqrt(d), RMSNorm as (1 + scale)); see the configuration file.

Memory: weights are made one layer at a time, in float32, and every sampled
sequence passes through a layer before the next layer is made, so the peak is
one layer's weights, the embedding and the hidden states.

``mode="fp8"`` is the control: every linear layer's input and weight are
rounded to float8 e4m3 (per-row and per-output-channel absmax scales) before
the float32 matmul, the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn
Q_BLOCK = 512  # query rows per attention block


def _normal(key, shape, fan_in):
    """The program's dense initialiser: N(0, 1/fan_in) in float32, stored bf16."""
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _swiglu_weights(key, d, f):
    k_up, k_down, k_gate = jax.random.split(key, 3)
    return {
        "w_up": _normal(k_up, (d, f), d),
        "w_down": _normal(k_down, (f, d), f),
        "w_gate": _normal(k_gate, (d, f), d),
    }


def root_keys(seed: int) -> jax.Array:
    """The eight keys the program's initialiser splits from the seed (made
    outside jit: a seed may exceed 32 bits)."""
    return jax.random.split(jax.random.PRNGKey(seed), 8)


def layer_keys(m: Dict, seed: int) -> jax.Array:
    """[num_layers, 2] keys: layer l's key, as the program's stacked init
    derives it (period 1: every layer is position 0 of its own period)."""
    return jax.random.split(jax.random.fold_in(root_keys(seed)[1], 0), m["num_layers"])


def embed_weights(m: Dict, key) -> jax.Array:
    """The embedding table from the first root key."""
    table = jax.random.normal(key, (m["vocab_size"], m["d_model"]), jnp.float32) * 0.02
    return table.astype(jnp.bfloat16)


def layer_weights(m: Dict, key) -> Dict:
    """One layer's weights (bf16 values; the router is float32)."""
    d, nh, nkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    k_attn, k_ffn, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    w = {
        "wq": _normal(kq, (d, nh, hd), d),
        "wk": _normal(kk, (d, nkv, hd), d),
        "wv": _normal(kv, (d, nkv, hd), d),
        "wo": _normal(ko, (nh, hd, d), nh * hd),
    }
    if m.get("num_experts", 0):
        E, f = m["num_experts"], m["d_ff_expert"]
        k_r, k_g, k_u, k_d, k_s = jax.random.split(k_ffn, 5)
        std = 1.0 / math.sqrt(d)
        w["router"] = jax.random.normal(k_r, (d, E), jnp.float32) * std
        w["experts"] = {
            "w_gate": _normal(k_g, (E, d, f), d),
            "w_up": _normal(k_u, (E, d, f), d),
            "w_down": _normal(k_d, (E, f, d), f),
        }
        if m.get("num_shared_experts", 0):
            w["shared"] = _swiglu_weights(k_s, d, m["num_shared_experts"] * f)
    else:
        w["ffn"] = _swiglu_weights(k_ffn, d, m["d_ff"])
    return w


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _f8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis`` (kept)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, mode, contract=1):
    """x [..., K] @ w [K, ...] over the first ``contract`` axes of w."""
    if mode == "fp8":
        x = _f8(x, axis=-1)
        w = _f8(w, axis=tuple(range(contract)))
    xs = x.reshape(*x.shape[: x.ndim - contract], -1) if contract > 1 else x
    ws = w.reshape(-1, *w.shape[contract:]) if contract > 1 else w
    return jnp.tensordot(xs, ws, axes=1)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [S, T, h, hd]; half-split rotary at positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(w, h, mode):
    g = _linear(h, w["w_gate"].astype(jnp.float32), mode)
    u = _linear(h, w["w_up"].astype(jnp.float32), mode)
    return _linear(jax.nn.silu(g) * u, w["w_down"].astype(jnp.float32), mode)


def _attention(w, h, m, mode):
    """Causal attention of h [S, T, d]; keys past a row's own position are
    masked, so right padding never reaches a real position.  Returns the
    output and the layer's K (post-RoPE) and V, [S, T, kv_heads, head_dim]."""
    S, T, _ = h.shape
    nh, nkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _rope(_linear(h, w["wq"].astype(jnp.float32), mode), m["rope_theta"])
    k = _rope(_linear(h, w["wk"].astype(jnp.float32), mode), m["rope_theta"])
    v = _linear(h, w["wv"].astype(jnp.float32), mode)
    k_cache, v_cache = k, v  # what a KV cache holds: post-RoPE keys, values
    k = jnp.repeat(k, nh // nkv, axis=2)  # query head j reads KV head j // (nh/nkv)
    v = jnp.repeat(v, nh // nkv, axis=2)
    qb = min(Q_BLOCK, T)
    nblk = T // qb
    kpos = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)  # [S, qb, nh, hd]
        s = jnp.einsum("sqhd,skhd->shqk", qi, k) * hd**-0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("shqk,skhd->sqhd", p, v)

    o = jax.lax.map(block, jnp.arange(nblk))  # [nblk, S, qb, nh, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(S, T, nh, hd)
    return _linear(o, w["wo"].astype(jnp.float32), mode, contract=2), k_cache, v_cache


def _moe(w, h, m, mode):
    """Softmax routing, top-k, gates renormalised over the k, every expert
    applied densely and weighted by its gate (zero where not chosen).  Also
    returns each token's routing margin: log(p_k / p_k+1), how far the k-th
    chosen expert leads the first one left out."""
    d = h.shape[-1]
    x = h.reshape(-1, d)
    k = m["top_k"]
    probs = jax.nn.softmax(x @ w["router"], axis=-1)  # router stays float32
    top, ids = jax.lax.top_k(probs, k + 1)
    margin = jnp.log(top[:, k - 1] / top[:, k])
    top, ids = top[:, :k], ids[:, :k]
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], ids].set(top)

    def expert(acc, e):
        we = jax.tree.map(lambda a: a[e], w["experts"])
        return acc + gate[:, e][:, None] * _swiglu(we, x, mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m["num_experts"]))
    if "shared" in w:
        y = y + _swiglu(w["shared"], x, mode)
    return y.reshape(h.shape), margin.reshape(h.shape[:-1])


def _layer(w, x, m, mode):
    eps = m["norm_eps"]
    a, k, v = _attention(w, _rmsnorm(x, eps), m, mode)
    x = x + a
    h = _rmsnorm(x, eps)
    if "experts" in w:
        ffn, margin = _moe(w, h, m, mode)
    else:
        ffn, margin = _swiglu(w["ffn"], h, mode), jnp.full(h.shape[:-1], jnp.inf)
    return x + ffn, k, v, margin


def _sq_err(a, b, rows):
    """(sum of squared a - b, sum of squared b) over each sequence's first
    ``rows`` positions."""
    keep = (jnp.arange(a.shape[1])[None, :] < rows[:, None])[..., None, None]
    d = jnp.where(keep, a - b, 0.0)
    return jnp.sum(d * d), jnp.sum(jnp.where(keep, b * b, 0.0))


def _logits(x, emb, m, mode):
    """The head's logits of hidden states x [..., d]."""
    return _linear(_rmsnorm(x, m["norm_eps"]), emb.T, mode)


def _head_gaps(x, emb, served, m, mode):
    """Per position: the reference's best logit minus its logit of the served
    token, and the token this mode ranks first."""
    logits = _logits(x, emb, m, mode)  # [S, P, V]
    best = jnp.argmax(logits, axis=-1)
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(logits, served[..., None], -1)[..., 0], best


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _bucket(n: int) -> int:
    return max(Q_BLOCK, -(-n // Q_BLOCK) * Q_BLOCK)


def _pad_kv(kvs: Sequence[Dict], kv: str, layer: int, T: int) -> np.ndarray:
    out = np.zeros((len(kvs), T, *kvs[0][kv].shape[2:]), np.float32)
    for i, c in enumerate(kvs):
        out[i, : c[kv].shape[1]] = c[kv][layer]
    return out


def _hidden(m: Dict, seed: int, tokens: np.ndarray, modes: Sequence[str], kv_idx=(), kv_prog=()):
    """Final hidden states [S, T, d] per mode, the float32 embedding, and
    each position's smallest routing margin over the layers in the float32
    reference [S, T] (inf for a dense stack); with ``kv_idx`` (sequences)
    and ``kv_prog`` (the program's K/V of each), also the worst relative
    error of K and V over the layers, per mode: the program against the
    float32 reference for ``f32``, the control against the float32
    reference for any other mode."""
    emb = jax.jit(embed_weights, static_argnums=(0,))(_Frozen(m), root_keys(seed)[0]).astype(jnp.float32)
    make_layer = jax.jit(layer_weights, static_argnums=(0,))
    run_layer = jax.jit(_layer, static_argnums=(2, 3))
    keys = layer_keys(m, seed)
    x0 = emb[jnp.asarray(tokens)] * math.sqrt(m["d_model"])
    xs = {mode: x0 for mode in modes}
    idx = jnp.asarray(np.asarray(kv_idx, np.int32))
    rows = jnp.asarray(np.asarray([c["k"].shape[1] for c in kv_prog], np.int32))
    kv_err = {mode: 0.0 for mode in modes}
    margin = jnp.full(tokens.shape, jnp.inf)
    for l in range(m["num_layers"]):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), make_layer(_Frozen(m), keys[l]))
        ref_kv = None
        for mode in modes:  # "f32" first
            xs[mode], k, v, mg = run_layer(w, xs[mode], _Frozen(m), mode)
            if mode == "f32":
                margin = jnp.minimum(margin, mg)
            if not len(kv_idx):
                continue
            got = {"k": k[idx], "v": v[idx]}
            if mode == "f32":
                ref_kv = got
                got = {kv: jnp.asarray(_pad_kv(kv_prog, kv, l, k.shape[1])) for kv in ("k", "v")}
            for kv in ("k", "v"):
                num, den = _sq_err(got[kv], ref_kv[kv], rows)
                kv_err[mode] = max(kv_err[mode], float(jnp.sqrt(num / den)))
        del w
    return xs, emb, kv_err, margin


def forward_logits(m: Dict, seed: int, tokens: np.ndarray, mode: str = "f32") -> np.ndarray:
    """Logits [S, T, V] of the reference over ``tokens`` [S, T] (tests)."""
    with jax.default_matmul_precision("highest"):
        xs, emb, _, _ = _hidden(m, seed, tokens, (mode,))
        return np.asarray(_linear(_rmsnorm(xs[mode], m["norm_eps"]), emb.T, mode))


def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare_served(
    m: Dict, seed: int, prompts: Sequence[np.ndarray], served: Sequence[np.ndarray],
    program_kv: Sequence = (), program_logits: Sequence = (), modes: Sequence[str] = ("f32",),
) -> Dict:
    """Teacher-forced reference over each prompt followed by its served tokens.

    ``gaps[mode]``: per request, the gap at every served position -- for
    ``f32`` the float32 reference's best logit minus its logit of the token
    the program served; for another mode the float32 gap of the token that
    mode (the control, put in the program's place) ranks first.
    ``kv_rel_err[mode]``: with ``program_kv`` (one entry per request, None
    or the K/V rows the program cached for prompt + served[:-1]), the worst
    over layers, K and V of ||cached - reference|| / ||reference||.
    ``logits_rel_err[mode]``: with ``program_logits`` (one entry per request,
    None or the program's logits [V] at the position that predicted the last
    served token), per such request ||logits - reference|| / ||reference||;
    for another mode, that mode's logits at the position.
    ``router_margin``: per such request, the smallest routing margin over
    the layers at that position (``_moe``), in the float32 reference.
    """
    if m.get("family") not in ("dense", "moe") or modes[0] != "f32":
        raise ValueError(f"reference covers dense/moe stacks and needs mode f32 first: {m.get('family')}, {modes}")
    S = len(prompts)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in zip(prompts, served)]
    T = _bucket(max(len(s) for s in seqs))
    tokens = np.zeros((S, T), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    # the positions whose logits predict a served token, right-padded
    P = max(len(s) for s in served)
    pos = np.zeros((S, P), np.int32)
    want = np.zeros((S, P), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        pos[i, : len(s)] = len(p) - 1 + np.arange(len(s))
        want[i, : len(s)] = s
    kv_idx = [i for i, c in enumerate(program_kv) if c is not None]
    kv_prog = [program_kv[i] for i in kv_idx]
    for i in kv_idx:
        if program_kv[i]["k"].shape[1] != len(seqs[i]):
            raise ValueError(f"request {i}: {program_kv[i]['k'].shape[1]} cached rows for {len(seqs[i])} tokens")

    with jax.default_matmul_precision("highest"):
        xs, emb, kv_err, margin = _hidden(m, seed, tokens, modes, kv_idx, kv_prog)
        head = jax.jit(_head_gaps, static_argnums=(3, 4))
        pos_d, want_d = jnp.asarray(pos), jnp.asarray(want)
        take = lambda x: jnp.take_along_axis(x, pos_d[..., None], axis=1)
        ref_gap, _ = head(take(xs["f32"]), emb, want_d, _Frozen(m), "f32")
        g = np.asarray(ref_gap)
        gaps: Dict[str, List[np.ndarray]] = {"f32": [g[i, : len(s)] for i, s in enumerate(served)]}
        for mode in modes[1:]:
            _, best = head(take(xs[mode]), emb, want_d, _Frozen(m), mode)
            # the float32 reference's gap of the token this mode puts first
            g, _ = head(take(xs["f32"]), emb, best, _Frozen(m), "f32")
            g = np.asarray(g)
            gaps[mode] = [g[i, : len(s)] for i, s in enumerate(served)]
        lg_idx = [i for i, lg in enumerate(program_logits) if lg is not None]
        lg_err, lg_margin = None, None
        if lg_idx:
            rows = jnp.asarray(lg_idx)
            last = jnp.asarray([len(prompts[i]) + len(served[i]) - 2 for i in lg_idx])
            head = jax.jit(_logits, static_argnums=(2, 3))
            ref = np.asarray(head(xs["f32"][rows, last], emb, _Frozen(m), "f32"))
            lg_err = {"f32": [_rel_err(np.asarray(program_logits[i], np.float32), ref[j])
                              for j, i in enumerate(lg_idx)]}
            for mode in modes[1:]:
                got = np.asarray(head(xs[mode][rows, last], emb, _Frozen(m), mode))
                lg_err[mode] = [_rel_err(got[j], ref[j]) for j in range(len(lg_idx))]
            lg_margin = [float(x) for x in np.asarray(margin[rows, last])]
    return {"gaps": gaps, "kv_rel_err": kv_err if kv_idx else None,
            "logits_rel_err": lg_err, "router_margin": lg_margin}


class _Frozen(dict):
    """A hashable view of the ``program`` block, so jit can take it static."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))

    def __eq__(self, other):
        return dict.__eq__(self, other)
