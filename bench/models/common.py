"""Pieces shared by the plain references of the benchmark's configurations.

Weights are made here, from the run's seed, and handed to the program as a
checkpoint would be; the reference makes them again from the same seed when
it checks the served tokens. Nothing here imports the program.

The reference is straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`. The lower-precision control that
the comparison has to fail is the same pass with every matrix product's
operands first rounded to the precision below the configuration's
(`CONTROL`): bfloat16 below float32, and below bfloat16 float8_e4m3fn with
a scale per row of the activations and one per weight tensor.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
FP8_MAX = 448.0
CONTROL = {"float32": "bf16", "bfloat16": "fp8"}


# -- weights -----------------------------------------------------------------

def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    k = jax.random.PRNGKey(np.uint32(lo))
    return jax.random.fold_in(k, np.uint32(hi))


def leaf_key(key: jax.Array, block: int, name: str) -> jax.Array:
    """Key of one named tensor of one block (0 = embedding and head)."""
    k = jax.random.fold_in(key, block)
    return jax.random.fold_in(k, np.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF))


def make_leaf(key, shape, init: str, fan_in: int, dtype):
    """One tensor: `w` normal/sqrt(fan_in), `norm` 1 + N(0, 0.1), `embed` N(0, 1)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "w":
        z = z * (fan_in ** -0.5)
    elif init == "norm":
        z = 1.0 + 0.1 * z
    elif init != "embed":
        raise ValueError(init)
    return z.astype(dtype)


def make_block(spec: Dict[str, Tuple], seed_key, block: int):
    """All tensors of one block, by name. spec: name -> (shape, init, fan_in, dtype)."""
    return {n: make_leaf(leaf_key(seed_key, block, n), *spec[n])
            for n in sorted(spec)}


_GEN = {}


def generate(spec: Dict[str, Tuple], seed: int, block: int):
    """One block's tensors made on the device in one jitted call."""
    sig = tuple((n, tuple(s[0]), s[1], s[2], jnp.dtype(s[3]).name)
                for n, s in sorted(spec.items()))
    fn = _GEN.get(sig)
    if fn is None:
        fn = _GEN[sig] = jax.jit(lambda k, b: make_block(spec, k, b))
    return fn(base_key(seed), jnp.uint32(block))


# -- arithmetic ----------------------------------------------------------------

def fake_fp8(x, axis):
    """Round x to float8_e4m3fn with an amax scale over `axis`, back to f32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm(eq: str, a, w, prec: str, w_axes=None):
    """einsum of activations `a` and weight `w` in float32, or through fp8
    (one scale per row of `a`, one per weight tensor or over `w_axes`)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if prec == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif prec == "fp8":
        a = fake_fp8(a, axis=-1)
        w = fake_fp8(w, axis=tuple(range(w.ndim)) if w_axes is None
                     else w_axes)
    return jnp.einsum(eq, a, w)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding on the two halves of the last axis.
    x: (B, T, H, D); positions: (B, T)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, scale):
    """q, k: (B, T, H, Dk); v: (B, T, H, Dv). Causal softmax attention."""
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def swiglu(x, wg, wu, wd, prec):
    g = mm("...d,df->...f", x, wg, prec)
    u = mm("...d,df->...f", x, wu, prec)
    return mm("...f,fd->...d", jax.nn.silu(g) * u, wd, prec)


def route(h, router, top_k, norm_topk):
    """Softmax over every expert's logit, then the top-k; gates are the
    chosen probabilities, renormalized when the configuration says so."""
    logits = jnp.einsum("td,de->te", h.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return ids, gates


def grouped_experts(h, ids, gates, valid, wg, wu, wd, prec):
    """Each token through the experts it routed to, grouped by expert.

    h: (N, d) f32; ids, gates: (N, k); valid: (N,) bool host array;
    w*: (E, d, f) / (E, f, d). The dispatch is built on the host from the
    routing, so each expert computes only its own tokens."""
    ids_h = np.asarray(ids)
    N, k = ids_h.shape
    E = wg.shape[0]
    tok = np.repeat(np.arange(N), k)
    choice = np.tile(np.arange(k), N)
    eid = ids_h.reshape(-1)
    keep = np.repeat(np.asarray(valid), k)
    tok, choice, eid = tok[keep], choice[keep], eid[keep]
    order = np.argsort(eid, kind="stable")
    tok, choice, eid = tok[order], choice[order], eid[order]
    counts = np.bincount(eid, minlength=E)
    cap = 8
    while cap < counts.max(initial=1):
        cap *= 2
    pos = np.arange(len(eid)) - np.repeat(np.cumsum(counts) - counts, counts)
    slot_tok = np.full((E, cap), N, np.int32)           # N = zero pad row
    slot_choice = np.zeros((E, cap), np.int32)
    slot_tok[eid, pos] = tok
    slot_choice[eid, pos] = choice
    return _grouped(h, gates, jnp.asarray(slot_tok), jnp.asarray(slot_choice),
                    wg, wu, wd, prec)


def _grouped_impl(h, gates, slot_tok, slot_choice, wg, wu, wd, prec):
    N, d = h.shape
    hp = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)], 0)
    x = hp[slot_tok]                                        # (E, C, d)
    g = mm("ecd,edf->ecf", x, wg, prec, (1, 2))
    u = mm("ecd,edf->ecf", x, wu, prec, (1, 2))
    y = mm("ecf,efd->ecd", jax.nn.silu(g) * u, wd, prec, (1, 2))
    gp = jnp.concatenate([gates, jnp.zeros((1, gates.shape[1]))], 0)
    w = gp[slot_tok, slot_choice]                           # (E, C)
    out = jnp.zeros((N + 1, d), jnp.float32)
    out = out.at[slot_tok].add(y * w[..., None])
    return out[:N]


_grouped = jax.jit(_grouped_impl, static_argnames="prec")


# -- the forward pass ----------------------------------------------------------

class Reference:
    """Logits of whole token sequences, one block of weights at a time.

    `mod` is a configuration's reference module: it names the tensors of
    each block (`top_spec`, `layer_spec`), says which layers route to
    experts (`is_moe`) and computes a layer's attention (`attention`).
    Each layer's weights are made from the seed when the layer runs and
    dropped after it, so the pass fits beside whatever else the process
    holds."""

    def __init__(self, mod, cfg: dict, prec: str = "f32"):
        assert prec in ("f32", "bf16", "fp8")
        self.mod, self.cfg, self.prec = mod, cfg, prec
        eps = cfg["rms_norm_eps"]

        def attn_block(w, x, positions):
            h = rms_norm(x, w["attn_norm"], eps)
            x = x + mod.attention(cfg, w, h, positions, prec)
            return x, rms_norm(x, w["ffn_norm"], eps)

        def dense_ffn(w, x, h):
            return x + swiglu(h, w["f_gate"], w["f_up"], w["f_down"], prec)

        def router(w, h):
            ids, gates = route(h, w["router"], cfg["num_experts_per_tok"],
                               cfg["norm_topk_prob"])
            return ids, gates * cfg.get("routed_scaling_factor", 1.0)

        def shared(w, h):
            return swiglu(h, w["s_gate"], w["s_up"], w["s_down"], prec)

        def head(top, x, rows, cols):
            h = rms_norm(x[rows, cols], top["final_norm"], eps)
            return mm("nd,dv->nv", h, top["lm_head"], prec)

        self._attn = jax.jit(attn_block)
        self._dense = jax.jit(dense_ffn)
        self._router = jax.jit(router)
        self._shared = jax.jit(shared)
        self._head = jax.jit(head)

    def logits(self, seed: int, tokens: np.ndarray, lengths: np.ndarray,
               rows: np.ndarray, cols: np.ndarray):
        """tokens: (R, T) int, each row valid up to its length; returns the
        (N, V) float32 logits at positions (rows[i], cols[i]), on the device."""
        mod, cfg, prec = self.mod, self.cfg, self.prec
        R, T = tokens.shape
        valid = (np.arange(T)[None, :] < np.asarray(lengths)[:, None]).reshape(-1)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (R, T))
        with jax.default_matmul_precision("highest"):
            top = generate(mod.top_spec(cfg), seed, 0)
            x = top["embed"][jnp.asarray(tokens)].astype(jnp.float32)
            for i in range(cfg["num_hidden_layers"]):
                w = generate(mod.layer_spec(cfg, i), seed, i + 1)
                x, h = self._attn(w, x, positions)
                if not mod.is_moe(cfg, i):
                    x = self._dense(w, x, h)
                    continue
                hf = h.reshape(R * T, -1)
                ids, gates = self._router(w, hf)
                y = grouped_experts(hf, ids, gates, valid, w["e_gate"],
                                    w["e_up"], w["e_down"], prec)
                if "s_gate" in w:
                    y = y + self._shared(w, hf)
                x = x + y.reshape(R, T, -1)
                del w
            return self._head(top, x, jnp.asarray(rows), jnp.asarray(cols))
