"""Plain float32 reference of the served MoE models, and its lower-precision
control.

It imports nothing of the program. It rebuilds the weights from the run's
seed by the recipe the program documents (``models/model.py::init_params``:
one ``PRNGKey(seed)`` split eight ways, normal draws in the served dtype
times fixed scales, zero norm offsets), keeps them in the served dtype, and
upcasts each layer to float32 as it runs. Every product runs at
``Precision.HIGHEST`` (on a TPU a float32 product is otherwise computed in
bfloat16 passes).

The forward pass: token embedding; per layer RMSNorm (scale ``1 + w``),
grouped-query attention with rotary phases on split halves and a causal
mask, a residual add, RMSNorm, a softmax router whose top-k gates are
renormalised to sum to one, SwiGLU experts computed densely and mixed by
those gates (nothing is ever dropped), a residual add; a final RMSNorm and
the output head (the embedding itself when tied). Each expert is held as
``expert_tp`` column slices of its hidden width, whose outputs add up to
the whole expert's. Departures from the published models are listed in
each configuration file under ``notes``.

The control is the same pass computed in float8: both operands of every
product of the layers and of the output head are rounded to e4m3 (one
scale per row of the activations, one per output channel of the weights),
the precision a later change might be tempted to serve in.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int
    top_k: int
    expert_ff: int
    expert_tp: int
    rope_theta: float
    eps: float
    tied: bool
    dtype: str

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 128) * 128

    @property
    def virtual_experts(self) -> int:
        return self.experts * self.expert_tp


def dims(cfg: dict) -> Dims:
    """Sizes of a configuration file (``bench/configs/*.json``)."""
    prog = cfg["program"]
    return Dims(
        layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        vocab=cfg["vocab_size"],
        experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["intermediate_size"],
        expert_tp=prog["expert_tp"],
        rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]),
        dtype=prog["dtype"],
    )


def init_weights(d: Dims, seed: int):
    """The served weights, in the served dtype, experts in virtual order."""
    dt = jnp.dtype(d.dtype)
    L, D, V = d.layers, d.d_model, d.padded_vocab
    H, KV, hd = d.heads, d.kv_heads, d.head_dim
    Ev, Fv = d.virtual_experts, d.expert_ff // d.expert_tp

    def make(key):
        keys = jax.random.split(key, 8)
        w = {"embed": jax.random.normal(keys[0], (V, D), dt) * 0.02}
        if not d.tied:
            w["lm_head"] = jax.random.normal(keys[1], (D, V), dt) * 0.02
        w["final_norm"] = jnp.zeros((D,), dt)
        w["ln1"] = jnp.zeros((L, D), dt)
        w["ln2"] = jnp.zeros((L, D), dt)
        ka = jax.random.split(keys[3], 4)
        s, so = float(1.0 / np.sqrt(D)), float(1.0 / np.sqrt(H * hd))
        w["wq"] = jax.random.normal(ka[0], (L, D, H * hd), dt) * s
        w["wk"] = jax.random.normal(ka[1], (L, D, KV * hd), dt) * s
        w["wv"] = jax.random.normal(ka[2], (L, D, KV * hd), dt) * s
        w["wo"] = jax.random.normal(ka[3], (L, H * hd, D), dt) * so
        km = jax.random.split(keys[4], 4)
        s_out = float(1.0 / np.sqrt(d.expert_ff))
        w["router"] = jax.random.normal(km[0], (L, D, d.experts), dt) * s
        w["w_gate"] = jax.random.normal(km[1], (L, Ev, D, Fv), dt) * s
        w["w_up"] = jax.random.normal(km[2], (L, Ev, D, Fv), dt) * s
        w["w_down"] = jax.random.normal(km[3], (L, Ev, Fv, D), dt) * s_out
        return w

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % 2**32))


def fp8(w, axis: int):
    """Round ``w`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis is reduced for the scale), back in float32."""
    w = w.astype(F32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attn(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def logits(w, tokens, d: Dims, control: bool = False):
    """(T,) token ids → (T, vocab) float32 logits of a causal forward pass."""
    T = tokens.shape[0]
    H, KV, hd, G = d.heads, d.kv_heads, d.head_dim, d.heads // d.kv_heads
    q8 = (lambda a, axis: fp8(a, axis)) if control else (
        lambda a, axis: a.astype(F32))

    def _mm(eq, a, b):  # activations a (rows first) times weights b
        return jnp.einsum(eq, q8(a, -1), b, precision=HIGHEST)

    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def layer(x, lw):
        h = _rms(x, lw["ln1"], d.eps)
        q = _mm("td,de->te", h, q8(lw["wq"], 0)).reshape(T, H, hd)
        k = _mm("td,de->te", h, q8(lw["wk"], 0)).reshape(T, KV, hd)
        v = _mm("td,de->te", h, q8(lw["wv"], 0)).reshape(T, KV, hd)
        q, k = _rope(q, pos, d.rope_theta), _rope(k, pos, d.rope_theta)
        s = _attn("tkgd,skd->kgts", q.reshape(T, KV, G, hd), k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = _attn("kgts,skd->tkgd", p, v).reshape(T, H * hd)
        x = x + _mm("te,ed->td", a, q8(lw["wo"], 0))
        h = _rms(x, lw["ln2"], d.eps)
        probs = jax.nn.softmax(_mm("td,de->te", h, q8(lw["router"], 0)), -1)
        top, ids = jax.lax.top_k(probs, d.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        gate = jnp.zeros_like(probs).at[pos[:, None], ids].set(top)  # (T, E)
        gate_v = jnp.repeat(gate, d.expert_tp, axis=1)  # (T, E_v)

        def expert(y, ew):
            g, u = (_mm("td,df->tf", h, q8(ew[n], 0)) for n in ("g", "u"))
            out = _mm("tf,fd->td", jax.nn.silu(g) * u, q8(ew["d"], 0))
            return y + out * ew["gate"][:, None], None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x), {
            "g": lw["w_gate"], "u": lw["w_up"], "d": lw["w_down"],
            "gate": gate_v.T})
        return x + y, None

    names = ("ln1", "ln2", "wq", "wk", "wv", "wo", "router", "w_gate",
             "w_up", "w_down")
    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in names})
    x = _rms(x, w["final_norm"], d.eps)
    if d.tied:
        out = _mm("td,vd->tv", x, q8(w["embed"], 1))
    else:
        out = _mm("td,dv->tv", x, q8(w["lm_head"], 0))
    return out[:, : d.vocab]


def served_sequence(prompt, served) -> tuple[np.ndarray, np.ndarray]:
    """The tokens fed to the model and the positions whose next token was
    served: a prompt of P tokens followed by n served tokens feeds
    ``prompt + served[:-1]`` and is judged at positions ``P-1 … P+n-2``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    fed = np.concatenate([prompt, served[:-1]])
    return fed, np.arange(len(prompt) - 1, len(fed))


def position_gaps(w, d: Dims, requests, pad_to: int, *, control: bool = False
                  ) -> list[np.ndarray]:
    """For each ``(prompt, served)`` pair, the gap at each judged position
    by which a token's logit lies below the reference's best. Without
    ``control`` the tokens judged are the served ones; with it, the tokens
    the float8 control puts first at the same positions of the same
    sequences."""
    out = []
    for prompt, served in requests:
        fed, at = served_sequence(prompt, served)
        toks = np.zeros(pad_to, np.int32)
        toks[: len(fed)] = fed
        ref = logits(w, jnp.asarray(toks), d)[at]
        if control:
            pick = jnp.argmax(logits(w, jnp.asarray(toks), d, True)[at], -1)
        else:
            pick = jnp.asarray(np.asarray(served, np.int32))
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        out.append(np.asarray(gap, np.float64))
    return out


def gap_stats(gaps: list[np.ndarray]) -> dict:
    """The numbers compared: the widest gap, the mean gap over every
    judged token, and the share of judged tokens (in per cent) that are
    not the reference's best by more than 0.05 logits."""
    if not gaps:
        return {}
    allg = np.concatenate(gaps)
    return {"widest_logit_gap": float(allg.max()),
            "mean_logit_gap": float(allg.mean()),
            "off_best_pct": float(100.0 * np.mean(allg > 0.05))}
