"""Plain reference for A.X-K1's block as one chip of an expert-parallel
deployment holds it, independent of the program.

A.X-K1 (``model_type`` axk1, ``https://huggingface.co/skt/A.X-K1``) is
DeepSeek-V3's block: latent attention (MLA) with a low-rank query, one
leading dense layer, then routed experts scored by a sigmoid beside one
shared expert. Per layer, on the residual stream ``x`` (eps
``rms_norm_eps``)::

    h = x + MLA(norm(x));  y = h + FFN(norm(h))
    MLA:  c_q = norm(W_qa x);  q = W_qb c_q  -> heads x (nope + rope)
          [c_kv ; k_r] = W_kva x;  c_kv = norm(c_kv)
          [k_nope ; v] = W_kvb c_kv  -> heads x (nope + v)
          rope on q_rope and on the one shared k_r: interleaved pairs
          (2i, 2i+1) turned by pos x inv_freq[i], YaRN inverse
          frequencies (NTK by parts), cos/sin unscaled
          (mscale / mscale_all_dim = 1)
          scores (q_nope . k_nope + q_rope . k_r) x (nope + rope)^-0.5
                 x (0.1 mscale_all_dim ln factor + 1)^2, causal softmax,
          o = W_o concat_h(P v)
    FFN, dense layers:   down(silu(gate(v)) * up(v)), width intermediate_size
    FFN, routed layers:  s = sigmoid(W_g v) over all n_routed_experts,
          in float32; the num_experts_per_tok largest s are selected
          (topk_method "none": plain top-k, no group limit, no bias);
          g_i = routed_scaling_factor x s_i / sum_selected s
          y = sum_i g_i E_i(v) + E_shared(v), every E a SwiGLU of width
          moe_intermediate_size

**The share.** The configuration says which experts this chip holds
(``experts_held`` from ``expert_offset``) and ``vocab_size`` is the
chip's slice. The router is ``n_routed_experts`` wide and selects over
all of them; ``sum_i`` runs over the selected experts *that are held*;
what the absent ones would add is left out, here as in the program, and
the partial result goes on to the next layer. No code stands in for the
absent chips.

``choice_margin``: per generated position the least, over the routed
layers, of ``references.choice_margin`` of that position's router logits
(sigmoid keeps their order) over the held experts.

All in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, the full forward over the
whole sequence from its first token for every new token: no cache (so
nothing a latent row written to a wrong slot could hide behind), no
absorbed projections (``k_nope`` and ``v`` are materialised per head),
no kernel, no batching trick, nothing imported from ``parallax_tpu``.
One layer's weights are upcast at a time, the experts one by one, the
heads in groups and the head's rows in slices.

Departures: the published code runs in bfloat16 and, on a whole
deployment, sums every selected expert; this reference is float32 and
sums the held ones. Nothing else departs.

The parameter tree is the program's, with the published names:
``self_attn.{q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
kv_a_layernorm, kv_b_proj, o_proj}``; a routed layer's ``mlp.gate``,
``mlp.experts.{gate,up,down}_proj`` stacked ``[held, out, in]`` and
``mlp.shared_expert``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import choice_margin

# Rows of the head upcast at a time; heads attended at a time.
HEAD_SLICE = 4096
HEAD_GROUP = 8
# What ``leave_out`` may name (the tests' wrong references).
PARTS = frozenset({"routed", "shared", "rope", "kv_norm"})


def _f32(p):
    return p["weight"].astype(jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None) -> np.ndarray:
    """Inverse frequencies of ``dim`` rotary dims: YaRN's NTK-by-parts
    (dims that turn more than ``beta_fast`` times over the original
    context keep their frequency, those under ``beta_slow`` turns are
    divided by ``factor``, a linear ramp between)."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return inv
    kind = scaling.get("rope_type") or scaling.get("type")
    if kind != "yarn":
        raise NotImplementedError(f"rope_scaling {kind!r}")
    factor = float(scaling["factor"])
    orig = float(scaling.get("original_max_position_embeddings", 4096))

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_dim(float(scaling.get("beta_slow", 1)))),
               dim // 2 - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv / factor * ramp + inv * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling") or {}
    all_dim = float(rs.get("mscale_all_dim", 0) or 0)
    if rs and all_dim and float(rs.get("factor", 1)) > 1:
        m = 0.1 * all_dim * math.log(float(rs["factor"])) + 1.0
        scale *= m * m
    return scale


def _rope(x, inv):
    """x [B, L, ..., D] at positions 0..L-1: pairs (2i, 2i+1) turned by
    ``pos x inv[i]``."""
    l = x.shape[1]
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv      # [L, D/2]
    ang = ang.reshape((1, l) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(v, gate, up, down):
    """``gate``/``up`` [I, H], ``down`` [H, I], any dtype."""
    g = v @ gate.astype(jnp.float32).T
    u = v @ up.astype(jnp.float32).T
    return (jax.nn.silu(g) * u) @ down.astype(jnp.float32).T


def _attention(a, v_in, *, heads, nope, rope, vdim, rank, inv, scale, eps,
               leave_out):
    b, l, _ = v_in.shape
    if "q_a_proj" in a:
        cq = _rms(v_in @ _f32(a["q_a_proj"]).T,
                  a["q_a_layernorm"]["weight"], eps)
        q = cq @ _f32(a["q_b_proj"]).T
    else:
        q = v_in @ _f32(a["q_proj"]).T
    q = q.reshape(b, l, heads, nope + rope)
    kva = v_in @ _f32(a["kv_a_proj_with_mqa"]).T
    c_kv, k_r = kva[..., :rank], kva[..., rank:]
    if "kv_norm" not in leave_out:
        c_kv = _rms(c_kv, a["kv_a_layernorm"]["weight"], eps)
    kv = (c_kv @ _f32(a["kv_b_proj"]).T).reshape(b, l, heads, nope + vdim)
    q_nope, q_r = q[..., :nope], q[..., nope:]
    k_nope, val = kv[..., :nope], kv[..., nope:]
    if "rope" not in leave_out:
        q_r, k_r = _rope(q_r, inv), _rope(k_r, inv)
    causal = jnp.tril(jnp.ones((l, l), bool))[None, None]

    def group(args):
        qn, qr, kn, vv = args                      # [B, L, G, .]
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
             + jnp.einsum("bqhd,bkd->bhqk", qr, k_r)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    g = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

    def split(t):
        return jnp.moveaxis(
            t.reshape(b, l, heads // g, g, t.shape[-1]), 2, 0)

    o = jax.lax.map(group, tuple(split(t) for t in
                                 (q_nope, q_r, k_nope, val)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, l, heads * vdim)
    return o @ _f32(a["o_proj"]).T


def _routed(m, v, *, top_k, held_from, norm_topk, scaling, leave_out):
    """The expert layer on ``v`` [N, hidden]: the held experts' part of
    the routed sum plus the shared expert, and the router's logits."""
    logits = v @ _f32(m["gate"]).T                     # [N, E] float32
    s = jax.nn.sigmoid(logits)
    top_s, top_i = jax.lax.top_k(s, top_k)
    g = top_s / jnp.sum(top_s, axis=-1, keepdims=True) if norm_topk else top_s
    g = g * scaling
    ex = m["experts"]
    held = ex["gate_proj"].shape[0]
    # g as [N, E] then this chip's columns: a token's gate on each held
    # expert, 0 where it was not selected.
    dense = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], top_i].set(g)
    mine = dense[:, held_from:held_from + held]        # [N, held]

    def one(acc, e):
        gate, up, down, w = e
        return acc + w[:, None] * _swiglu(v, gate, up, down), None

    out = jnp.zeros_like(v)
    if "routed" not in leave_out:
        out, _ = jax.lax.scan(one, out, (
            ex["gate_proj"], ex["up_proj"], ex["down_proj"], mine.T))
    if "shared_expert" in m and "shared" not in leave_out:
        sh = m["shared_expert"]
        out = out + _swiglu(v, sh["gate_proj"]["weight"],
                            sh["up_proj"]["weight"], sh["down_proj"]["weight"])
    return out, logits


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vdim", "rank", "scale", "eps", "top_k",
    "held_from", "norm_topk", "scaling", "leave_out"))
def layer_forward(lp, x, inv, *, heads, nope, rope, vdim, rank, scale, eps,
                  top_k, held_from, norm_topk, scaling,
                  leave_out=frozenset()):
    """One block on ``x`` [B, L, hidden] (float32), full causal attention
    over the L positions: ``(x, router logits [B, L, E] | None)``."""
    with jax.default_matmul_precision("highest"):
        b, l, hid = x.shape
        v_in = _rms(x, lp["input_layernorm"]["weight"], eps)
        x = x + _attention(
            lp["self_attn"], v_in, heads=heads, nope=nope, rope=rope,
            vdim=vdim, rank=rank, inv=inv, scale=scale, eps=eps,
            leave_out=leave_out)
        v_in = _rms(x, lp["post_attention_layernorm"]["weight"], eps)
        m = lp["mlp"]
        if "experts" not in m:
            return x + _swiglu(v_in, m["gate_proj"]["weight"],
                               m["up_proj"]["weight"],
                               m["down_proj"]["weight"]), None
        out, logits = _routed(
            m, v_in.reshape(b * l, hid), top_k=top_k, held_from=held_from,
            norm_topk=norm_topk, scaling=scaling, leave_out=leave_out)
        return x + out.reshape(b, l, hid), logits.reshape(b, l, -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w_norm, w_head, *, eps):
    """Logits of ``x`` [B, hidden]: final norm, the head's rows upcast
    in slices."""
    with jax.default_matmul_precision("highest"):
        x = _rms(x, w_norm, eps)
        v, h = w_head.shape
        if v <= HEAD_SLICE or v % HEAD_SLICE:
            return x @ w_head.astype(jnp.float32).T
        parts = jax.lax.map(lambda w: x @ w.astype(jnp.float32).T,
                            w_head.reshape(v // HEAD_SLICE, HEAD_SLICE, h))
        return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], v)


def logits_at(params, cfg: dict, ids: np.ndarray, at: np.ndarray,
              leave_out=frozenset()):
    """Float32 logits [B, vocab] at position ``at[b]`` of each row of
    ``ids`` [B, L], and the routed layers' router logits there
    ``[layers, B, E]``. Every block is causal, so whatever follows
    ``at`` is irrelevant and one program serves every step of a row
    shape."""
    unknown = set(leave_out) - PARTS
    if unknown:
        raise ValueError(f"leave_out names {sorted(unknown)}, not {sorted(PARTS)}")
    if cfg.get("scoring_func", "softmax") != "sigmoid" or cfg.get(
            "topk_method", "none") not in ("none", "greedy"):
        raise NotImplementedError(
            "this reference scores with a sigmoid and selects the plain "
            "top-k (topk_method \"none\")")
    inv = jnp.asarray(yarn_inv_freq(
        int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"]),
        cfg.get("rope_scaling")), jnp.float32)
    rows = jnp.arange(ids.shape[0])
    x = params["embed_tokens"]["weight"][jnp.asarray(ids)].astype(jnp.float32)
    routers = []
    for lp in params["layers"]:
        x, router = layer_forward(
            lp, x, inv, heads=int(cfg["num_attention_heads"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), vdim=int(cfg["v_head_dim"]),
            rank=int(cfg["kv_lora_rank"]), scale=softmax_scale(cfg),
            eps=float(cfg["rms_norm_eps"]),
            top_k=int(cfg["num_experts_per_tok"]),
            held_from=int(cfg.get("expert_offset", 0) or 0),
            norm_topk=bool(cfg.get("norm_topk_prob", True)),
            scaling=float(cfg.get("routed_scaling_factor", 1.0)),
            leave_out=frozenset(leave_out))
        if router is not None:
            routers.append(router[rows, jnp.asarray(at)])
    logits = _head(x[rows, jnp.asarray(at)], params["norm"]["weight"],
                   params["lm_head"]["weight"],
                   eps=float(cfg["rms_norm_eps"]))
    if not routers:
        raise ValueError("this reference is of a stage with routed layers")
    return logits, jnp.stack(routers)


def held_experts(params, cfg: dict) -> np.ndarray:
    """Indices of the experts this chip holds, from the tree itself."""
    for lp in params["layers"]:
        if "experts" in lp["mlp"]:
            n = lp["mlp"]["experts"]["gate_proj"].shape[0]
            start = int(cfg.get("expert_offset", 0) or 0)
            return np.arange(start, start + n)
    return np.arange(0)


def greedy_continuations(params, cfg: dict, prompts: list[list[int]],
                         n_new: int, leave_out=frozenset()) -> list[dict]:
    """Continue each prompt ``n_new`` tokens by the reference's own
    argmax. Returns, per prompt, the tokens, their logprobs, at every
    step the gap between the best and the second-best logit, and
    ``choice_margin`` (module docstring)."""
    b = len(prompts)
    plen = len(prompts[0])
    if any(len(p) != plen for p in prompts):
        raise ValueError("reference prompts share one length")
    held = held_experts(params, cfg)
    k = int(cfg["num_experts_per_tok"])
    ids = np.zeros((b, plen + n_new), np.int32)
    ids[:, :plen] = np.asarray(prompts, np.int32)
    out = [{"prompt": list(map(int, p)), "tokens": [], "logprobs": [],
            "top2_gap": [], "choice_margin": []} for p in prompts]
    for step in range(n_new):
        at = np.full((b,), plen + step - 1, np.int32)
        logits, routers = logits_at(params, cfg, ids, at, leave_out)
        lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
        tok = lps.argmax(-1)
        ids[:, plen + step] = tok
        # The least over the routed layers of a position's own pass.
        margin = choice_margin(np.asarray(routers), k, held).min(axis=0)
        for i in range(b):
            out[i]["tokens"].append(int(tok[i]))
            out[i]["logprobs"].append(float(lps[i, tok[i]]))
            out[i]["top2_gap"].append(float(top2[i, 1] - top2[i, 0]))
            out[i]["choice_margin"].append(float(margin[i]))
    return out
