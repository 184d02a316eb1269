"""Plain reference for the dense Qwen2 block, independent of the program.

RMSNorm, biased q/k/v projections, rotary embedding (rotate-half), grouped
causal attention, SwiGLU, a tied or untied head: float32 ``jax.numpy``
with ``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching trick. It follows the Hugging Face ``Qwen2ForCausalLM`` forward;
there is no departure from it.

Parameters arrive in the tree the server is given (``layers[i].self_attn
.q_proj.weight`` is ``[out, in]``, as in the checkpoint). One layer's
weights are upcast at a time, so the reference fits beside a 13 GB stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _linear(x, p):
    y = x @ p["weight"].astype(jnp.float32).T
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: [B, L, H, D]; rotate-half convention."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, :, None].astype(jnp.float32) * inv     # [B, L, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "theta", "eps"))
def layer_forward(lp, x, *, hq, hkv, theta, eps):
    """One decoder layer on ``x`` [B, L, hidden] (float32), full causal
    attention over the L positions."""
    with jax.default_matmul_precision("highest"):
        b, l, _ = x.shape
        h = _rms(x, lp["input_layernorm"]["weight"], eps)
        a = lp["self_attn"]
        q = _linear(h, a["q_proj"]).reshape(b, l, hq, -1)
        k = _linear(h, a["k_proj"]).reshape(b, l, hkv, -1)
        v = _linear(h, a["v_proj"]).reshape(b, l, hkv, -1)
        d = q.shape[-1]
        pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = hq // hkv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        causal = jnp.tril(jnp.ones((l, l), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, l, hq * d)
        x = x + _linear(o, a["o_proj"])
        h = _rms(x, lp["post_attention_layernorm"]["weight"], eps)
        m = lp["mlp"]
        act = jax.nn.silu(_linear(h, m["gate_proj"])) * _linear(h, m["up_proj"])
        return x + _linear(act, m["down_proj"])


@jax.jit
def _embed(w, ids):
    return w[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, eps):
    return _rms(x, w, eps)


@jax.jit
def _head_chunk(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(jnp.float32).T


def logits_at(params, cfg: dict, ids: np.ndarray, at: np.ndarray):
    """Float32 logits [B, V] at position ``at[b]`` of each row of ``ids``
    [B, L]. Causal attention makes whatever follows ``at`` irrelevant, so
    rows are padded to one length and one program serves every step."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    x = _embed(params["embed_tokens"]["weight"], jnp.asarray(ids))
    for lp in params["layers"]:
        x = layer_forward(lp, x, hq=hq, hkv=hkv,
                          theta=float(cfg["rope_theta"]),
                          eps=float(cfg["rms_norm_eps"]))
    x = x[jnp.arange(ids.shape[0]), jnp.asarray(at)]
    x = _final_norm(x, params["norm"]["weight"], float(cfg["rms_norm_eps"]))
    head = (params.get("lm_head") or params["embed_tokens"])["weight"]
    # The head in slices of the vocabulary: a float32 copy of a
    # [152064, 3584] matrix would be 2.2 GB.
    step = -(-head.shape[0] // 8)
    return jnp.concatenate(
        [_head_chunk(x, head[i:i + step]) for i in range(0, head.shape[0], step)],
        axis=-1,
    )


def greedy_continuations(params, cfg: dict, prompts: list[list[int]],
                         n_new: int) -> list[dict]:
    """Continue each prompt ``n_new`` tokens by the reference's own argmax.
    Returns, per prompt, the tokens, their logprobs, and at every step the
    gap between the best and the second-best logit."""
    b = len(prompts)
    plen = len(prompts[0])
    if any(len(p) != plen for p in prompts):
        raise ValueError("reference prompts share one length")
    ids = np.zeros((b, plen + n_new), np.int32)
    ids[:, :plen] = np.asarray(prompts, np.int32)
    out = [{"prompt": list(map(int, p)), "tokens": [], "logprobs": [],
            "top2_gap": []} for p in prompts]
    for step in range(n_new):
        at = np.full((b,), plen + step - 1, np.int32)
        logits = logits_at(params, cfg, ids, at)
        lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
        tok = lps.argmax(-1)
        ids[:, plen + step] = tok
        for i in range(b):
            out[i]["tokens"].append(int(tok[i]))
            out[i]["logprobs"].append(float(lps[i, tok[i]]))
            out[i]["top2_gap"].append(float(top2[i, 1] - top2[i, 0]))
    return out
