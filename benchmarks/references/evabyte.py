"""Plain reference for EvaByte's block, independent of the program.

EVA (Zheng et al., "Efficient Attention via Control Variates", ICLR 2023)
as EvaByte's ``eva_pt_ref.py`` instantiates it, with learned,
input-independent proposals. With ``W = window_size``, ``C = chunk_size``,
``s = head_dim ** -0.5`` and, per head, the learned vectors ``mu``
(``adaptive_mu_k``) and ``phi`` (``adaptive_phi``):

    h      = x / sqrt(mean(x^2) + eps) * (1 + w_norm)
    q,k,v  = h Wq, h Wk, h Wv          (no bias; rotate-half RoPE on q, k)
    chunk c = tokens 16c .. 16c+15:
       k~_c = sum_j softmax_j(<mu, k_j>) k_j     v~_c = sum_j softmax_j(<phi, k_j>) v_j
    query i, w = i // W:
       exact   E_i = { j <= i : j // W == w }
       chunks  C_i = { c : c < (W / C) * w }
       o_i = one softmax over { s q_i.k_j, j in E_i } and { s q_i.k~_c, c in C_i }
    x <- x + o Wo;   x <- x + SwiGLU(norm(x));   logits = (norm(x) W_head)[:, :vocab]

all in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
computed directly from every key of the sequence with an explicit mask
and explicit chunk sums: no cache, no paging, no kernel, nothing imported
from ``parallax_tpu``. Queries go through attention, and rows through
the MLP and the projections, in blocks, and one layer's weights are
upcast at a time, so a 6k prompt at the published widths fits beside a
6.5 GB stage.

Departures from the published forward pass (``modeling_evabyte.py`` /
``eva_pt_ref.py``), each an inference where the public ``config.json`` is
silent (the configuration file lists them under ``assumed``):

* ``mu`` weights the keys' sum and ``phi`` the values' sum (not the
  reverse), and neither chunk logit carries a further scale.
* RoPE is applied before the chunks are summarised (the summaries are
  built from the keys as the exact part sees them).
* A window's chunks become visible only to queries of later windows; a
  query sees no summary of its own window, complete chunks included.
* The published code runs in bfloat16 with float32 islands
  (``fp32_skip_add``, ``fp32_logits``, ``mixedp_attn``); this reference
  is float32 throughout, which is what those islands approximate.
* Of the 8 prediction heads (``num_pred_heads``; ``W_head`` has
  ``8 * vocab`` rows) only head 0, rows ``0 .. vocab-1``, is read: the
  next byte. Heads 1-7 feed multi-byte self-speculation, which the
  program does not run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Rows per block: queries in attention, tokens in the projections and MLP.
BLOCK = 512


def _f32(p):
    return p["weight"].astype(jnp.float32)


def _rms(x, w, eps):
    """``norm_add_unit_offset``: the weight is an offset from one."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x: [L, H, D] at positions 0..L-1; rotate-half convention."""
    l, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv      # [L, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocked(fn, x):
    """``fn`` over blocks of rows of ``x`` [L, ...] (L a multiple of
    BLOCK or smaller than it)."""
    l = x.shape[0]
    if l <= BLOCK:
        return fn(x)
    return jax.lax.map(fn, x.reshape(l // BLOCK, BLOCK, *x.shape[1:])
                       ).reshape(l, -1)


def chunk_summaries(k, v, mu, phi, chunk):
    """k, v: [L, H, D] -> (k~, v~) [L // chunk, H, D]: the two
    softmax-weighted sums of every whole chunk."""
    n = k.shape[0] // chunk
    kc = k[: n * chunk].reshape(n, chunk, *k.shape[1:])
    vc = v[: n * chunk].reshape(n, chunk, *v.shape[1:])
    wk = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    wv = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", wk, kc),
            jnp.einsum("nch,nchd->nhd", wv, vc))


def eva_attention(q, k, v, mu, phi, *, window, chunk, with_summaries=True):
    """q, k, v: [L, H, D] after RoPE -> o [L, H, D], by the equations at
    the top. ``with_summaries=False`` drops the chunk part (the tests'
    wrong reference)."""
    l, h, d = q.shape
    scale = d ** -0.5
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)
    n_chunks = ks.shape[0]
    j = jnp.arange(l)
    c = jnp.arange(n_chunks)
    per_window = window // chunk

    def block(args):
        qb, ib = args                                   # [B, H, D], [B]
        exact = (j[None, :] <= ib[:, None]) & (
            j[None, :] // window == ib[:, None] // window)
        s_e = jnp.einsum("bhd,jhd->hbj", qb, k) * scale
        s_e = jnp.where(exact[None], s_e, -jnp.inf)
        seen = c[None, :] < per_window * (ib[:, None] // window)
        if not with_summaries:
            seen = jnp.zeros_like(seen)
        s_c = jnp.einsum("bhd,chd->hbc", qb, ks) * scale
        s_c = jnp.where(seen[None], s_c, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([s_e, s_c], axis=-1), axis=-1)
        return (jnp.einsum("hbj,jhd->bhd", p[..., :l], v)
                + jnp.einsum("hbc,chd->bhd", p[..., l:], vs))

    if l <= BLOCK:
        return block((q, j))
    n = l // BLOCK
    out = jax.lax.map(block, (q.reshape(n, BLOCK, h, d),
                              j.reshape(n, BLOCK)))
    return out.reshape(l, h, d)


@functools.partial(jax.jit, static_argnames=(
    "heads", "theta", "eps", "window", "chunk", "with_summaries"))
def layer_forward(lp, x, *, heads, theta, eps, window, chunk,
                  with_summaries=True):
    """One decoder layer on ``x`` [L, hidden] (float32; L below BLOCK or
    a multiple of it)."""
    with jax.default_matmul_precision("highest"):
        a, m = lp["self_attn"], lp["mlp"]
        l = x.shape[0]
        wq, wk, wv = _f32(a["q_proj"]), _f32(a["k_proj"]), _f32(a["v_proj"])
        w_in = lp["input_layernorm"]["weight"]

        def qkv(xb):
            hb = _rms(xb, w_in, eps)
            return jnp.concatenate([hb @ wq.T, hb @ wk.T, hb @ wv.T], -1)

        q, k, v = jnp.split(_blocked(qkv, x), 3, axis=-1)
        q, k, v = (t.reshape(l, heads, -1) for t in (q, k, v))
        q, k = _rope(q, theta), _rope(k, theta)
        o = eva_attention(
            q, k, v, a["adaptive_mu_k"].astype(jnp.float32),
            a["adaptive_phi"].astype(jnp.float32),
            window=window, chunk=chunk, with_summaries=with_summaries,
        ).reshape(l, -1)
        wo = _f32(a["o_proj"])
        x = x + _blocked(lambda ob: ob @ wo.T, o)
        wg, wu, wd = _f32(m["gate_proj"]), _f32(m["up_proj"]), _f32(m["down_proj"])
        w_post = lp["post_attention_layernorm"]["weight"]

        def mlp(xb):
            hb = _rms(xb, w_post, eps)
            return (jax.nn.silu(hb @ wg.T) * (hb @ wu.T)) @ wd.T

        return x + _blocked(mlp, x)


@functools.partial(jax.jit, static_argnames=("eps", "vocab"))
def _head(x, w_norm, w_head, *, eps, vocab):
    with jax.default_matmul_precision("highest"):
        logits = _rms(x, w_norm, eps) @ w_head.astype(jnp.float32).T
        return logits[:, :vocab]            # head 0 of num_pred_heads


def logits_at(params, cfg: dict, ids: np.ndarray, at: np.ndarray,
              with_summaries: bool = True):
    """Float32 logits [B, vocab] at position ``at[b]`` of each row of
    ``ids`` [B, L]. The mask is causal, so whatever follows ``at`` is
    irrelevant: rows are padded to one length (a multiple of BLOCK once
    past it) and one program serves every step."""
    heads = cfg["num_attention_heads"]
    b, l = ids.shape
    pad = (-l) % BLOCK if l > BLOCK else 0
    out = []
    for i in range(b):
        row = np.concatenate([ids[i], np.zeros((pad,), ids.dtype)])
        x = params["embed_tokens"]["weight"][jnp.asarray(row)].astype(
            jnp.float32)
        for lp in params["layers"]:
            x = layer_forward(
                lp, x, heads=heads, theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                window=int(cfg["window_size"]), chunk=int(cfg["chunk_size"]),
                with_summaries=with_summaries,
            )
        out.append(_head(
            x[int(at[i])][None], params["norm"]["weight"],
            params["lm_head"]["weight"],
            eps=float(cfg["rms_norm_eps"]), vocab=int(cfg["vocab_size"]),
        )[0])
    return jnp.stack(out)


def greedy_continuations(params, cfg: dict, prompts: list[list[int]],
                         n_new: int, with_summaries: bool = True
                         ) -> list[dict]:
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
        logits = logits_at(params, cfg, ids, at, with_summaries)
        lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
        tok = lps.argmax(-1)
        ids[:, plen + step] = tok
        for i in range(b):
            out[i]["tokens"].append(int(tok[i]))
            out[i]["logprobs"].append(float(lps[i, tok[i]]))
            out[i]["top2_gap"].append(float(top2[i, 1] - top2[i, 0]))
    return out
