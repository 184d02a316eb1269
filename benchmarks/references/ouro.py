"""Plain reference for Ouro's looped stack, independent of the program.

Ouro (Zhu et al., "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741) as the published ``modeling_ouro.py``
instantiates it: ``L = num_hidden_layers`` blocks applied ``U =
total_ut_steps`` times a token with the same weights every pass, a
sandwich norm on both branches of a block, and the model's final norm
closing every pass:

    x = Embed[token]
    for u in 0..U-1:
        for l in 0..L-1:
            a = Attn_l(norm_1(x));   x = x + norm_2(a)
            m = MLP_l(norm_3(x));    x = x + norm_4(m)
        x = norm_final(x)            # pass u's result, pass u+1's input
    logits = W_head x                # after the last pass; untied head
    norm:      v / sqrt(mean(v^2) + eps) * w
    Attn:      q = Wq v [Hq x D], k = Wk v [Hkv x D], v = Wv v [Hkv x D], no bias,
               no qk-norm; rotate-half RoPE (rope_theta) over the whole head on
               q and k; causal softmax(q k^T / sqrt(D)) v;  Wo
    MLP:       down(silu(gate(v)) * up(v)), no bias

all in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
the full forward over the whole sequence from its first token for every
new token: no cache (so nothing a pass could share with another or miss
from an earlier chunk), no kernel, no batching trick, nothing imported
from ``parallax_tpu``. One layer's weights are upcast at a time and the
head's rows in slices.

Departures from the published forward pass: the published code also
evaluates an exit gate ``sigmoid(w_gate . x_u + b_gate)`` after every
pass from which a row may leave before the last; the configuration sets
``early_exit_threshold`` 1, at which none does and the logits are the
last pass's, so the gate (``early_exit_gate`` in the tree) is not read
here. The published code runs in bfloat16; this reference is float32
throughout. Nothing else departs.

The parameter tree is the program's, with the published names:
``layers[l]`` holds ``input_layernorm`` (norm_1), ``input_layernorm_2``
(norm_2), ``post_attention_layernorm`` (norm_3),
``post_attention_layernorm_2`` (norm_4), ``self_attn`` and ``mlp``;
``norm`` is the final norm, ``lm_head`` the head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Rows of the head upcast at a time.
HEAD_SLICE = 8192
# What ``leave_out`` may name (the tests' wrong references): the norm
# that closes a pass, the norm on attention's branch, the norm on the
# MLP's branch, the last pass as a whole.
PARTS = frozenset({"pass_norm", "attn_branch_norm", "mlp_branch_norm",
                   "last_pass"})


def _f32(p):
    return p["weight"].astype(jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: [B, L, H, D] at positions 0..L-1; rotate-half convention."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # [L, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "leave_out"))
def layer_forward(lp, x, *, heads, kv_heads, theta, eps,
                  leave_out=frozenset()):
    """One block on ``x`` [B, L, hidden] (float32), full causal
    attention over the L positions."""
    with jax.default_matmul_precision("highest"):
        b, l, _ = x.shape
        v_in = _rms(x, lp["input_layernorm"]["weight"], eps)
        a = lp["self_attn"]
        q = (v_in @ _f32(a["q_proj"]).T).reshape(b, l, heads, -1)
        k = (v_in @ _f32(a["k_proj"]).T).reshape(b, l, kv_heads, -1)
        v = (v_in @ _f32(a["v_proj"]).T).reshape(b, l, kv_heads, -1)
        d = q.shape[-1]
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None, None], s,
                      -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        branch = o.reshape(b, l, heads * d) @ _f32(a["o_proj"]).T
        if "attn_branch_norm" not in leave_out:
            branch = _rms(branch, lp["input_layernorm_2"]["weight"], eps)
        x = x + branch
        v_in = _rms(x, lp["post_attention_layernorm"]["weight"], eps)
        m = lp["mlp"]
        branch = (jax.nn.silu(v_in @ _f32(m["gate_proj"]).T)
                  * (v_in @ _f32(m["up_proj"]).T)) @ _f32(m["down_proj"]).T
        if "mlp_branch_norm" not in leave_out:
            branch = _rms(branch, lp["post_attention_layernorm_2"]["weight"],
                          eps)
        return x + branch


@functools.partial(jax.jit, static_argnames=("eps",))
def _pass_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(x, w_head):
    """Logits of ``x`` [B, hidden] (already normed), the head's rows
    upcast in slices."""
    with jax.default_matmul_precision("highest"):
        v, h = w_head.shape
        if v <= HEAD_SLICE or v % HEAD_SLICE:
            return x @ w_head.astype(jnp.float32).T
        parts = jax.lax.map(lambda w: x @ w.astype(jnp.float32).T,
                            w_head.reshape(v // HEAD_SLICE, HEAD_SLICE, h))
        return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], v)


def logits_at(params, cfg: dict, ids: np.ndarray, at: np.ndarray,
              leave_out=frozenset()):
    """Float32 logits [B, vocab] at position ``at[b]`` of each row of
    ``ids`` [B, L]. Every block is causal, so whatever follows ``at`` is
    irrelevant and one program serves every step of a row shape."""
    unknown = set(leave_out) - PARTS
    if unknown:
        raise ValueError(f"leave_out names {sorted(unknown)}, not {sorted(PARTS)}")
    leave_out = frozenset(leave_out)
    eps = float(cfg["rms_norm_eps"])
    passes = int(cfg.get("total_ut_steps") or 1)
    if "last_pass" in leave_out:
        passes -= 1
    x = params["embed_tokens"]["weight"][jnp.asarray(ids)].astype(jnp.float32)
    for u in range(passes):
        for lp in params["layers"]:
            x = layer_forward(
                lp, x, heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                theta=float(cfg["rope_theta"]), eps=eps,
                leave_out=leave_out)
        # The final norm closes every pass; the last one's output is
        # what the head reads.
        if "pass_norm" not in leave_out or u == passes - 1:
            x = _pass_norm(x, params["norm"]["weight"], eps=eps)
    x = x[jnp.arange(ids.shape[0]), jnp.asarray(at)]
    head = params.get("lm_head") or params["embed_tokens"]
    return _head(x, head["weight"])


def greedy_continuations(params, cfg: dict, prompts: list[list[int]],
                         n_new: int, leave_out=frozenset()) -> list[dict]:
    """Continue each prompt ``n_new`` tokens by the reference's own
    argmax. Returns, per prompt, the tokens, their logprobs, and at every
    step the gap between the best and the second-best logit."""
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
        logits = logits_at(params, cfg, ids, at, leave_out)
        lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
        tok = lps.argmax(-1)
        ids[:, plen + step] = tok
        for i in range(b):
            out[i]["tokens"].append(int(tok[i]))
            out[i]["logprobs"].append(float(lps[i, tok[i]]))
            out[i]["top2_gap"].append(float(top2[i, 1] - top2[i, 0]))
    return out
