"""Plain references that configurations bring with them.

A configuration whose block the dense Qwen2 reference
(``benchmarks/harness/reference.py``) does not cover names its own in
its file: ``"bench": {"reference": {"module": "<stem>", "rows": [...]}}``
is ``benchmarks/references/<stem>.py``. The module has one entry,

    greedy_continuations(params, cfg, prompts, new_tokens) -> list[dict]

with ``params`` the tree the server is given (bf16, checkpoint names and
layouts), ``cfg`` the configuration's ``config.json`` as run, ``prompts``
a list of token lists of one length. It returns, per prompt, ``{"prompt",
"tokens", "logprobs", "top2_gap"}``: the prompt, ``new_tokens`` tokens by
the reference's own argmax, their log-probabilities, and at every step
the gap between its two best logits (``server.replay_reference`` reads
these).

The rules are the dense block's: plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the published forward pass
with every departure noted, no kernel, cache or batching trick, nothing
imported from ``parallax_tpu`` and nothing taken that the program made.
One layer's weights upcast at a time, the head in slices, so that it fits
beside the stage. The child runs it before ``serve`` sizes its KV pool.
"""
