"""Plain reference of the served dense transformer, in float32 jax.numpy,
independent of the program: it imports nothing of `repro` and takes none
of its weights, scales or tables. A configuration file names it under
`reference` (cell.py), and the harness reaches it only as `Cell.reference`.

Interface (every reference module provides it):
  Dims.from_config(conf)   sizes from the configuration file
  Dims.from_arch(cfg)      sizes from the program's ModelConfig (tests)
  check(cfg, conf)         refuses a model this module does not cover, or
                           one whose sizes differ from the file's
  make_weights(dims, seed)
  served_gaps(dims, w, prompt, served, *, control=False)
  decode_token_flops(dims, context)   model FLOPs of one decoded token

Semantics (those of the repo's dense decoder, which the configuration
files list beside the published model's departures):
  x = embed[tokens]                      tied embedding (V_pad, d)
  per layer:  h = rmsnorm(x) (weight 1 + w, w = 0 at init)
              q = h wq, k = h deq4(wk), v = h deq4(wv)
              rotate-half RoPE (theta) on q and k
              k, v stored as int4 with one scale per token and head
              softmax(q k^T / sqrt(hd)) v, causal, grouped heads
              x += o wo
              h = rmsnorm(x); x += (silu(h deq4(w_gate)) * h deq4(w_up)) w_down
  logits = rmsnorm(x) embed^T over the real vocab
deq4(w): int4 levels round(w / s), clipped to [-7, 7], times
s = max|w| / 7 over the input dim, one scale per output column.

Weights are made from the seed as a deployment's checkpoint would be
fixed: leaf i of the parameter tree (keys in sorted order) is
N(0, 1) from fold_in(PRNGKey(seed), i), over sqrt(fan_in) (every dim
but the last), in bf16; the norms are zeros.

`low=True` is the control: the same computation in float8 (e4m3) where
the configuration computes in bfloat16, the precision step below it:
the bf16 weights, the residual stream after every add, every linear
layer's input, and q, k and v are rounded to float8.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
INT4_MAX = 7


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"], c["vocab_size"],
                   float(c.get("rope_theta", 10000.0)),
                   float(c.get("assumed", {}).get("rms_norm_eps", 1e-6)))

    @classmethod
    def from_arch(cls, cfg) -> "Dims":
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.hd, cfg.d_ff, cfg.vocab, cfg.rope_theta, cfg.norm_eps)


# configuration file key -> the program's ModelConfig attribute
CONFIG_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
               "num_attention_heads": "n_heads",
               "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
               "intermediate_size": "d_ff", "vocab_size": "vocab",
               "rope_theta": "rope_theta"}


def check(cfg, conf: dict) -> None:
    """Refuse a program model outside the tied SwiGLU dense decoder, or
    one whose sizes differ from those the configuration file states."""
    if cfg.family != "dense" or cfg.act != "swiglu" \
            or not cfg.tie_embeddings or cfg.qkv_bias:
        raise ValueError(f"{cfg.name}: the reference covers the tied "
                         f"SwiGLU dense decoder only")
    for key, attr in CONFIG_KEYS.items():
        if key in conf and getattr(cfg, attr) != conf[key]:
            raise ValueError(f"{conf['repro_arch']}: {key} is "
                             f"{getattr(cfg, attr)} in the program, "
                             f"{conf[key]} in the configuration file")


def matmul_params(m: Dims) -> int:
    """Weights one token multiplies through: attention projections,
    a SwiGLU MLP per layer, and the logits head over the real vocab."""
    attn = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim \
        + m.n_heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return m.n_layers * (attn + mlp) + m.d_model * m.vocab


def decode_token_flops(m: Dims, context: int) -> int:
    """Model FLOPs of one decoded token that attends to `context` tokens:
    2 per weight it multiplies through, plus 4 * head_dim per query head,
    layer and attended token."""
    return 2 * matmul_params(m) \
        + 4 * m.n_layers * m.n_heads * m.head_dim * context


def leaves(m: Dims) -> list[tuple[str, tuple, bool]]:
    """(name, shape, random) of every parameter, in the order the tree's
    sorted keys give them (the index each leaf's key is folded with)."""
    n, d, H, KV, hd, f = (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads,
                          m.head_dim, m.d_ff)
    return [("embed", (m.vocab_padded, d), True),
            ("final_norm", (d,), False),
            ("attn_norm", (n, d), False),
            ("wk", (n, d, KV * hd), True),
            ("wo", (n, H * hd, d), True),
            ("wq", (n, d, H * hd), True),
            ("wv", (n, d, KV * hd), True),
            ("mlp_norm", (n, d), False),
            ("w_down", (n, f, d), True),
            ("w_gate", (n, d, f), True),
            ("w_up", (n, d, f), True)]


@functools.partial(jax.jit, static_argnames=("shape",))
def _draw(key, index, shape):
    fan = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
    w = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    # a true division: XLA may not turn it into a product by 1/sqrt(fan)
    den = jax.lax.optimization_barrier(jnp.float32(np.sqrt(fan)))
    return (w / den).astype(jnp.bfloat16)


def make_weights(m: Dims, seed: int, device=None) -> dict:
    """The bf16 weights of the seed, one leaf at a time on `device`."""
    key = jax.random.PRNGKey(seed)
    out = {}
    with jax.default_device(device or jax.devices()[0]):
        for i, (name, shape, random) in enumerate(leaves(m)):
            if random:
                out[name] = _draw(key, i, shape)
    return out


def _deq4(w):
    """int4 per-output-column quantize-dequantize of a (K, N) weight."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8) \
        / INT4_MAX
    return jnp.clip(jnp.round(w / s), -INT4_MAX, INT4_MAX) * s


def _kv4(x):
    """int4 quantize-dequantize with one scale per token and head."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) \
        / INT4_MAX
    return jnp.clip(jnp.round(x / s), -INT4_MAX, INT4_MAX) * s


def _f8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def forward(m: Dims, w: dict, tokens, *, low: bool = False):
    """tokens (S,) int32 -> logits (S, V_pad) float32 (padded ids -inf)."""
    act = _f8 if low else (lambda x: x)

    def wt(x):               # a bf16 weight, as the computation reads it
        return act(x.astype(jnp.float32))

    def lin(x, wmat):
        return jnp.dot(act(x), wmat, precision=HI)

    S = tokens.shape[0]
    H, KV, hd = m.n_heads, m.n_kv_heads, m.head_dim
    x = act(w["embed"][tokens].astype(jnp.float32))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def layer(x, p):
        h = _rms(x, m.norm_eps)
        q = lin(h, wt(p["wq"])).reshape(S, H, hd)
        k = lin(h, _deq4(p["wk"])).reshape(S, KV, hd)
        v = lin(h, _deq4(p["wv"])).reshape(S, KV, hd)
        q, k = act(_rope(q, m.rope_theta)), act(_rope(k, m.rope_theta))
        k, v = _kv4(k), _kv4(act(v))
        qg = q.reshape(S, KV, H // KV, hd)
        s = jnp.einsum("qkhd,skd->khqs", qg, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("khqs,skd->qkhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HI).reshape(S, H * hd)
        x = act(x + lin(o, wt(p["wo"])))
        h = _rms(x, m.norm_eps)
        mid = jax.nn.silu(lin(h, _deq4(p["w_gate"]))) \
            * lin(h, _deq4(p["w_up"]))
        return act(x + lin(mid, wt(p["w_down"]))), None

    stacked = {k: w[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                 "w_down")}
    x, _ = jax.lax.scan(layer, x, stacked)
    logits = lin(_rms(x, m.norm_eps), wt(w["embed"]).T)
    col = jnp.arange(m.vocab_padded)
    return jnp.where(col[None, :] < m.vocab, logits, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _gaps(m: Dims, w: dict, tokens, targets, live, *, control: bool):
    """Per position, the gap by which the reference's logit of the token
    judged lies below its best: the target token (the program's), or
    with `control` the token the float8 computation puts first."""
    ref = forward(m, w, tokens)
    best = jnp.max(ref, axis=-1)
    if control:
        targets = jnp.argmax(forward(m, w, tokens, low=True), axis=-1)
    got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    return jnp.where(live, best - got, 0.0)


def served_gaps(m: Dims, w: dict, prompt: np.ndarray, served: list[int], *,
                control: bool = False, bucket: int = 256) -> np.ndarray:
    """Gaps at every served position of one request, teacher-forced on
    its prompt and served tokens: the prediction at position P - 1 + i
    is judged against served token i. Lengths pad to `bucket` so that
    few programs compile; padding lies after the judged positions."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)])[:-1]
    P, n = len(prompt), len(served)
    S = -(-len(seq) // bucket) * bucket
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(S, np.int32)
    targets[P - 1:P - 1 + n] = served
    live = np.zeros(S, bool)
    live[P - 1:P - 1 + n] = True
    g = _gaps(m, w, jnp.asarray(tokens), jnp.asarray(targets),
              jnp.asarray(live), control=control)
    return np.asarray(g)[P - 1:P - 1 + n]
