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

**A model that chooses inside itself** (top-k routed experts, top-k
blocks of a sparse attention, any ``top_k`` over scores) returns one
more key a row, ``choice_margin``: one non-negative float per generated
position, as many as ``tokens``. It is the reference's own statement of
how near that position's forward pass came to choosing otherwise, and
``choice_margin`` below is its arithmetic (``choice_distances`` a
candidate's):

- at every layer that selects, over the candidates *whose selection
  changes what this stage computes* — a whole model: all of them; a
  chip's share of an expert-parallel deployment: the experts it holds,
  since which of the absent experts a token went to changes nothing
  here —
- the distance from the candidate's score to the selection boundary on
  its other side: selected, its score less the best unselected one;
  unselected, the worst selected one less its own;
- on the scores as they are ranked, before any squashing: the router's
  logits where the selection is their top-k (sigmoid and softmax keep
  the order), the biased scores themselves where a bias enters after
  the squashing; a selection in stages (groups, then experts) counts
  every stage;
- divided by the standard deviation of those scores over all the
  candidates of that layer and position, so that it has no unit;
- and of all that the least, over the layers, of the compared
  position's own forward pass only. A flip at an earlier token reaches
  it through attention, and no margin sees that: with a share of the
  experts held it is rare and small, with every expert held it is
  neither (PERF.md section 2), and the rule does not cover such a
  stage unless its routed layer is the last.

``server.replay_reference`` calls a position under ``server.CHOICE_TIE``
unsure and holds it to nothing: a bf16 program and a float32 reference
that rank the same scores will, within rounding of the boundary, rank
them otherwise, and the position then differs by a whole expert with
nothing wrong on either side. Every other position is held to what a
row without the key is held to, and ``server.SURE_MIN`` of them must be
left: list rows enough in ``bench.reference.rows``. Margins cannot
excuse a reference: all 0 leaves no sure position and the run is not
correct. A row without the key is sure at every position.

The rules are the dense block's: plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the published forward pass
with every departure noted, no kernel, cache or batching trick, nothing
imported from ``parallax_tpu`` and nothing taken that the program made.
One layer's weights upcast at a time, the head in slices, so that it fits
beside the stage. The child runs it before ``serve`` sizes its KV pool.
"""

import numpy as np


def choice_distances(scores, k: int) -> np.ndarray:
    """Each candidate's distance to the boundary of the top-``k`` of its
    row of ``scores`` [..., candidates], on its other side and in
    standard deviations of the row: a selected candidate's score less
    the best unselected one, an unselected one's worst selected score
    less its own."""
    scores = np.asarray(scores, np.float64)
    n = scores.shape[-1]
    if not 0 < k < n:
        raise ValueError(f"top-{k} of {n} candidates chooses nothing")
    ranked = np.sort(scores, axis=-1)
    worst_in, best_out = ranked[..., n - k, None], ranked[..., n - k - 1, None]
    return np.where(scores >= worst_in, scores - best_out,
                    worst_in - scores) / scores.std(axis=-1, keepdims=True)


def choice_margin(scores, k: int, held=None) -> np.ndarray:
    """How near each row of ``scores`` [..., candidates] stands to
    choosing another top-``k``: the least of ``choice_distances`` over
    ``held``, the candidates whose selection changes what this stage
    computes, as indices into the last axis (None: all; infinite where
    ``held`` is empty). The least over a position's layers is what a
    row's ``choice_margin`` lists."""
    distance = choice_distances(scores, k)
    if held is not None:
        distance = distance[..., np.asarray(held, np.intp)]
    return distance.min(axis=-1, initial=np.inf)
