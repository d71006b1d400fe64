"""Open-loop questions about shared documents, the schedule fixed by rule
for every seed: ``generators/open_loop_ruled.py``'s parts (stratified
lengths and exponential-quantile gaps, each list permuted by
``np.random.default_rng(mix["order_seed"])``) for requests that are one of
a few long documents followed by a short question of the asker's own.

A request's prompt is ``document + question``. The documents' lengths are
the stratified quantiles ``(i + 0.5) / count`` of a log-uniform
distribution, rounded to a multiple of ``round_to`` (the page size: a
document is whole pages, so the pages it publishes are exactly its own);
their token ids, and every question's, come from ``--seed``, nothing is
shared beyond the document. Documents are taken round-robin in the rule's
permutation, so each is asked equally often.

The lead-in has two parts. From ``-lead_seconds`` one first question a
document, ``publish.every_seconds`` apart, each answered by
``publish.answer_tokens`` tokens: when it finishes, the engine publishes the
document's pages to its prefix cache. From ``-steady_lead_seconds`` the
steady mix, which the window continues: its questions find their documents
cached and prefill their own tokens alone.

Mix parameters (``benchmarks/traffic/<mix>.json``): ``open_loop_ruled``'s
(``prompt_tokens`` is the whole prompt's range, for the runner's warm-up
and its check), and
  documents: {"count", "dist": "loguniform", "min", "max", "round_to"}
  question_tokens: a length distribution
  publish: {"every_seconds", "answer_tokens"}
  steady_lead_seconds: the steady mix begins this long before the window
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmarks.generators import open_loop
from benchmarks.generators.open_loop import Arrival, _gap_quantile


def quantile(dist: Dict[str, Any], u: float) -> float:
    """``open_loop.quantile``, and a log-uniform distribution's."""
    if dist["dist"] == "loguniform":
        return dist["min"] * (dist["max"] / dist["min"]) ** u
    return open_loop.quantile(dist, u)


def lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    return np.array([int(round(quantile(dist, (i + 0.5) / n)))
                     for i in range(n)])


def document_lengths(mix: Dict[str, Any]) -> List[int]:
    docs = mix["documents"]
    to = int(docs["round_to"])
    return [int(round(x / to)) * to for x in lengths(docs, int(docs["count"]))]


def _steady(mix, rate_per_s, start, span, order):
    """(due, question length, answer length) of the ``round(rate x span)``
    requests inside [start, start + span), as ``open_loop_ruled._part``."""
    n = max(1, int(round(rate_per_s * span)))
    gaps = np.array([_gap_quantile(mix["arrivals"], (i + 0.5) / n)
                     for i in range(n)])
    gaps = order.permutation(gaps * span / gaps.sum())
    due = start + np.cumsum(gaps) - gaps[0]
    questions = order.permutation(lengths(mix["question_tokens"], n))
    answers = order.permutation(lengths(mix["output_tokens"], n))
    return list(zip(due.tolist(), questions.tolist(), answers.tolist()))


def schedule(mix: Dict[str, Any], rate_per_s: float, seconds: float):
    """The rule's part of the traffic, the same for every seed: [(due,
    document, question length, answer length)]."""
    order = np.random.default_rng(int(mix["order_seed"]))
    n_docs = int(mix["documents"]["count"])
    turn = order.permutation(n_docs)
    lead, steady = float(mix["lead_seconds"]), float(mix["steady_lead_seconds"])
    pub = mix["publish"]
    first = order.permutation(lengths(mix["question_tokens"], n_docs))
    out = [(-lead + i * float(pub["every_seconds"]), int(turn[i]),
            int(first[i]), int(pub["answer_tokens"])) for i in range(n_docs)]
    asked = 0
    for start, span in ((-steady, steady), (0.0, seconds)):
        for due, q, a in _steady(mix, rate_per_s, start, span, order):
            out.append((due, int(turn[asked % n_docs]), q, a))
            asked += 1
    return sorted(out, key=lambda r: r[0])


def generate(mix: Dict[str, Any], rate_per_s: float, seconds: float,
             seed: int, vocab_size: int) -> List[Arrival]:
    ids = np.random.default_rng(seed)
    docs = [ids.integers(1, vocab_size, (n,)).tolist()
            for n in document_lengths(mix)]
    return [Arrival(float(due), docs[d] + ids.integers(
        1, vocab_size, (q,)).tolist(), a)
        for due, d, q, a in schedule(mix, rate_per_s, seconds)]
