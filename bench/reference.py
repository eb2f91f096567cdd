"""Plain reference of the benchmarked model and of its QAD step.

A dense GQA decoder (RMSNorm, fused QKV with bias, RoPE, causal softmax
attention, SwiGLU MLP, tied or separate output head), written in plain
``jax.numpy`` from the published architecture and the NVFP4 format.  It
imports nothing of the program under test and takes nothing that the
program made: weights come from ``weights.make`` and the seed, NVFP4
rounding is done here from the format's definition.

It computes in the precision the configuration states.  Every tensor that
the configuration keeps in bfloat16 (weights, activations between
operations, logits) is rounded by ``Precision.store``; matmuls accumulate
in float32; norms, RoPE and softmax compute in float32.  GEMM operands of
the quantized kinds (attention and MLP projections) go through NVFP4
fake quantization with a straight-through gradient: weights blocked by 16
along the contraction axis with one tensor scale per layer, and, where the
configuration quantizes activations, activations blocked by 16 along
their last axis with a dynamic tensor scale over the scope it states.

``Precision(lower=True)`` is the control: the same model with every
bfloat16 store rounded through float8 e4m3, the next precision below.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

F32, BF16 = jnp.float32, jnp.bfloat16
E4M3 = jnp.float8_e4m3fn
E4M3_MAX, E2M1_MAX, BLOCK = 448.0, 6.0, 16


@dataclasses.dataclass(frozen=True)
class Precision:
    lower: bool = False        # the control: bf16 stores go through e4m3

    def store(self, x):
        x = x.astype(BF16)
        if self.lower:        # rounded forward, the gradient passes as is
            low = jnp.clip(x, -E4M3_MAX, E4M3_MAX).astype(E4M3).astype(BF16)
            x = x + jax.lax.stop_gradient(low - x)
        return x


# ---------------------------------------------------------------------------
# NVFP4 fake quantization, from the format's definition
# ---------------------------------------------------------------------------


def _e2m1(a):
    """Round magnitudes to the E2M1 grid {0, .5, 1, 1.5, 2, 3, 4, 6},
    ties to the even code."""
    a = jnp.clip(a, 0.0, E2M1_MAX)
    step = jnp.where(a < 2.0, 0.5, jnp.where(a < 4.0, 1.0, 2.0))
    return jnp.round(a / step) * step


def nvfp4_qdq(x, amax):
    """Quantize ``x`` to NVFP4 along its last axis and back (float32).
    ``amax`` (broadcastable to x without its last axis) sets the tensor
    scale amax / (448 * 6); each block of 16 gets an E4M3 scale."""
    xf = x.astype(F32)
    *lead, k = xf.shape
    xb = xf.reshape(*lead, k // BLOCK, BLOCK)
    s_t = jnp.maximum(amax.astype(F32), 1e-30) / (E4M3_MAX * E2M1_MAX)
    s_t = s_t[..., None, None] if s_t.ndim else s_t
    s_b = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / E2M1_MAX / s_t
    s_b = jnp.clip(s_b, 2.0 ** -6, E4M3_MAX).astype(E4M3).astype(F32)
    s = s_b * s_t
    y = xb / jnp.maximum(s, 1e-30)
    return (jnp.sign(y) * _e2m1(jnp.abs(y)) * s).reshape(xf.shape)


@jax.custom_vjp
def _ste(x, xq):
    return xq


def _ste_fwd(x, xq):
    return xq, None


def _ste_bwd(_, g):
    return g, None


_ste.defvjp(_ste_fwd, _ste_bwd)


def quant_act(x, scope, prec: Precision):
    """NVFP4 fake quantization of a GEMM input [B, S, K].

    ``scope`` is "tensor" (one amax over the whole tensor, as in training),
    or an int array [B] of prompt lengths: positions inside the prompt
    share one amax per request (the prefill call), every later position
    has its own (a decode step).  With ``scope`` None (a configuration
    that keeps activations in bfloat16) GEMM inputs are not quantized.
    """
    xf = jax.lax.stop_gradient(x.astype(F32))
    if isinstance(scope, str):
        amax = jnp.max(jnp.abs(xf))
    else:
        pos = jnp.arange(x.shape[1])[None, :]
        in_prompt = pos < scope[:, None]
        tok = jnp.max(jnp.abs(xf), axis=-1)                     # [B, S]
        prompt = jnp.max(jnp.where(in_prompt, tok, 0.0), axis=-1,
                         keepdims=True)
        amax = jnp.where(in_prompt, prompt, tok)
    return _ste(x, prec.store(nvfp4_qdq(xf, amax)))


def quant_weight(w, prec: Precision):
    """NVFP4 fake quantization of one layer's [K, N] weight, blocked along
    K, with one tensor scale for the slice."""
    wt = jax.lax.stop_gradient(w.astype(F32)).T
    return _ste(w, prec.store(nvfp4_qdq(wt, jnp.max(jnp.abs(wt))).T))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rmsnorm(x, w, eps, prec):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return prec.store(y * w.astype(F32))


def _rope(x, theta):
    """x [B, S, H, hd] at positions 0..S-1, rotating half against half."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2].astype(F32), x[..., hd // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gemm(x, w, quant, scope, prec):
    if quant:
        w = quant_weight(w, prec)
        if scope is not None:
            x = quant_act(x, scope, prec)
    return prec.store(jnp.einsum("bsk,kn->bsn", x, w,
                                 preferred_element_type=F32))


def _attention(q, k, v, prec):
    """Causal softmax attention; q [B,S,H,hd], k/v [B,S,Hkv,hd]."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=F32) * (1.0 / jnp.sqrt(F32(hd)))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    m = jnp.max(sc, -1, keepdims=True)
    p = jnp.exp(sc - m)
    out = jnp.einsum("bhqk,bkhd->bqhd", prec.store(p), v,
                     preferred_element_type=F32)
    return prec.store(out / jnp.moveaxis(jnp.sum(p, -1), 1, 2)[..., None])


def _layer(dims, quant, scope, prec, x, p):
    hd, h, hkv = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    b, s, _ = x.shape
    eps = dims["rms_norm_eps"]
    a_in = _rmsnorm(x, p["ln1"]["w"], eps, prec)
    qkv = prec.store(_gemm(a_in, p["wqkv"], quant, scope, prec) + p["bqkv"])
    q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
    q = prec.store(_rope(q.reshape(b, s, h, hd), dims["rope_theta"]))
    k = prec.store(_rope(k.reshape(b, s, hkv, hd), dims["rope_theta"]))
    o = _attention(q, k, v.reshape(b, s, hkv, hd), prec)
    x = prec.store(x + _gemm(o.reshape(b, s, h * hd), p["wo"], quant, scope,
                             prec))
    m_in = _rmsnorm(x, p["ln2"]["w"], eps, prec)
    gate = _gemm(m_in, p["wg"], quant, scope, prec)
    up = _gemm(m_in, p["wu"], quant, scope, prec)
    act = prec.store(prec.store(jax.nn.silu(gate)) * up)
    return prec.store(x + _gemm(act, p["wd"], quant, scope, prec))


def hidden(dims, params, tokens, quant, scope, prec):
    """Final-normed hidden states [B, S, d] of a teacher-forced pass."""
    params = jax.tree.map(prec.store, params)
    x = params["embed"][tokens]
    body = jax.checkpoint(functools.partial(_layer, dims, quant, scope, prec))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, params["layers"])
    return _rmsnorm(x, params["final_norm"]["w"], dims["rms_norm_eps"], prec)


def unembed(dims, params, prec):
    w = params["embed"].T if dims["tie_embeddings"] else params["lm_head"]
    return prec.store(w)


def logits(dims, params, tokens, quant, scope, prec):
    """[B, S, V] logits (float32 view of the bfloat16 values)."""
    h = hidden(dims, params, tokens, quant, scope, prec)
    return prec.store(jnp.einsum("bsd,dv->bsv", h,
                                 unembed(dims, params, prec),
                                 preferred_element_type=F32)).astype(F32)


# ---------------------------------------------------------------------------
# the QAD step: KL(teacher || NVFP4 student), T = 1, and AdamW
# ---------------------------------------------------------------------------


def _kl_rows(t_logits, s_logits):
    p_t = jax.nn.softmax(t_logits, -1)
    return jnp.sum(p_t * (jax.nn.log_softmax(t_logits, -1)
                          - jax.nn.log_softmax(s_logits, -1)), -1)


def qad_loss(dims, student, teacher, batch, prec, chunk=512):
    """Masked mean KL over tokens.  The vocabulary-wide head runs over
    blocks of ``chunk`` positions so that no [S, V] float32 tensor of the
    whole sequence is live at once."""
    h_s = hidden(dims, student, batch["tokens"], True, "tensor", prec)
    h_t = jax.lax.stop_gradient(
        hidden(dims, teacher, batch["tokens"], False, "tensor", prec))
    w_s = unembed(dims, student, prec)
    w_t = jax.lax.stop_gradient(unembed(dims, teacher, prec))
    b, s, d = h_s.shape
    chunk = min(chunk, s)
    n = s // chunk

    def block(args):
        hs, ht = args
        ls = prec.store(jnp.einsum("bsd,dv->bsv", hs, w_s,
                                   preferred_element_type=F32)).astype(F32)
        lt = prec.store(jnp.einsum("bsd,dv->bsv", ht, w_t,
                                   preferred_element_type=F32)).astype(F32)
        return _kl_rows(lt, ls)

    split = lambda x: jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    kl = jax.lax.map(jax.checkpoint(block), (split(h_s), split(h_t)))
    kl = jnp.moveaxis(kl, 0, 1).reshape(b, s)
    mask = batch["mask"].astype(F32)
    return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    clip_norm: float = 1.0

    def init(self, params):
        z = lambda p: jnp.zeros(p.shape, F32)
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}

    def apply(self, params, grads, state, step):
        """One update; returns (params, state).  The gradient is clipped to
        a global norm of ``clip_norm`` first; params stay bfloat16, the
        update is computed in float32 and added before the rounding."""
        g = jax.tree.map(lambda x: x.astype(F32), grads)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, self.clip_norm
                                      / jnp.maximum(norm, 1e-12)), g)
        t = step + 1.0
        m = jax.tree.map(lambda m, g: self.b1 * m + (1 - self.b1) * g,
                         state["m"], g)
        v = jax.tree.map(lambda v, g: self.b2 * v + (1 - self.b2) * g * g,
                         state["v"], g)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        new = jax.tree.map(
            lambda p, m, v: (p + -self.lr * ((m / c1) / (
                jnp.sqrt(v / c2) + self.eps))).astype(p.dtype),
            params, m, v)
        return new, {"m": m, "v": v}


def make_qad_step(dims, opt: AdamW, prec: Precision):
    """step(student, teacher, opt_state, batch, step) ->
    (student, opt_state, loss)."""

    def step(student, teacher, state, batch, i):
        loss, grads = jax.value_and_grad(qad_loss, argnums=1)(
            dims, student, teacher, batch, prec)
        student, state = opt.apply(student, grads, state, i)
        return student, state, loss

    return jax.jit(step, donate_argnums=(0, 2))
