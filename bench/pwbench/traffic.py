"""The one general generator: a corpus, questions, arrivals and upserts
from ``--seed`` and the parameters of a mix's data file.

Every seed gets the same work in another order: the same multiset of
passage lengths, of question lengths and of gaps between arrivals. What
differs between seeds is the words, the pairing, and the order of the
questions and of the gaps."""

from __future__ import annotations

import math
import re
from typing import Any

import numpy as np

# --- the hash tokenizer the served models use, restated here so that the
# reference depends on nothing of the program (copied from
# pathway_tpu/models/tokenizer.py; listed under Open questions in PERF.md)
_WORD_RE = re.compile(r"[a-z0-9]+", re.IGNORECASE)
_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK = 0xFFFFFFFFFFFFFFFF
_memo: dict[tuple[str, int], int] = {}


def _word_id(word: str, vocab: int) -> int:
    got = _memo.get((word, vocab))
    if got is None:
        h = _FNV_OFFSET
        for byte in word.encode():
            h = ((h ^ byte) * _FNV_PRIME) & _MASK
        got = _memo[(word, vocab)] = 2 + h % (vocab - 2)
    return got


def tokenize(text: str, vocab: int, max_len: int) -> list[int]:
    """ids: 0 pad, 1 class token, words hashed into [2, vocab)."""
    ids = [1]
    for m in _WORD_RE.finditer(text.lower()):
        ids.append(_word_id(m.group(0), vocab))
        if len(ids) >= max_len:
            break
    return ids


# --- the prompt the server builds (xpacks/llm/prompts.py DEFAULT_QA_TEMPLATE)
QA_TEMPLATE = (
    "Answer the question based only on the context below. If the context "
    "does not contain the answer, reply exactly: No information found.\n\n"
    "Context:\n{context}\n\nQuestion: {query}\nAnswer:"
)


def build_prompt(texts: list[str], question: str) -> str:
    return QA_TEMPLATE.format(context="\n\n".join(texts), query=question)


TEMPLATE_WORDS = len(_WORD_RE.findall(QA_TEMPLATE.format(context="", query="")))


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """n whole numbers evenly spread over [lo, hi]: the same for every
    seed."""
    if n == 1:
        return np.asarray([(lo + hi) // 2])
    return np.floor(lo + (hi - lo + 1) * (np.arange(n) + 0.5) / n).astype(int)


class Corpus:
    """Passages of made-up words, so two passages share few words. Every
    seed has the same multiset of passage lengths, spread evenly from
    ``words_min`` to ``words_max`` (the configurations give both as 100:
    the disjoint 100-word passages of the DPR and RAG papers' corpus)."""

    def __init__(self, seed: int, cfg: dict):
        rng = np.random.default_rng([int(seed), 11])
        self.cfg = cfg
        n = int(cfg["passages"])
        self.vocabulary = int(cfg["vocabulary_words"])
        lengths = spread(cfg["words_min"], cfg["words_max"], n)
        self.lengths = lengths[rng.permutation(n)]
        self._rng = rng
        self.texts: dict[int, str] = {}  # doc id -> live text
        self.version: dict[int, int] = {}
        self.history: dict[str, tuple[int, int]] = {}  # text -> (id, version)
        for i in range(n):
            self._write(i, 0)
        self.next_id = n

    def _write(self, doc_id: int, version: int) -> str:
        n_words = int(self.lengths[doc_id % len(self.lengths)])
        words = self._rng.integers(0, self.vocabulary, n_words - 1)
        text = f"p{doc_id}v{version} " + " ".join(f"w{int(w)}" for w in words)
        self.texts[doc_id] = text
        self.version[doc_id] = version
        self.history[text] = (doc_id, version)
        return text

    def replace(self, doc_id: int) -> str:
        """A new version of a live document (a retraction and an insert)."""
        return self._write(doc_id, self.version[doc_id] + 1)

    def append(self) -> tuple[int, str]:
        doc_id = self.next_id
        self.next_id += 1
        return doc_id, self._write(doc_id, 0)

    def question(self, rng: Any, doc_id: int, n_words: int) -> str:
        words = self.texts[doc_id].split()[1:]
        picked = [words[int(j)] for j in rng.integers(0, len(words), n_words)]
        return " ".join(picked)

    def prompt_token_range(self, topk: int, question_words: list[int]) -> tuple[int, int]:
        """Fewest and most tokens a prompt of this corpus can have."""
        lo = 1 + TEMPLATE_WORDS + topk * int(self.cfg["words_min"]) + question_words[0]
        hi = 1 + TEMPLATE_WORDS + topk * int(self.cfg["words_max"]) + question_words[1]
        return lo, hi


def make_questions(seed: int, corpus: Corpus, mix: dict, n: int) -> list[dict]:
    """n questions of ``question_words`` words (the same multiset of
    lengths for every seed), each about a passage of the initial corpus,
    the passages visited in rounds of a permutation."""
    rng = np.random.default_rng([int(seed), 23])
    lo, hi = mix["question_words"]
    qlens = spread(lo, hi, max(n, 1))[rng.permutation(max(n, 1))]
    initial = int(corpus.cfg["passages"])
    out = []
    order: list[int] = []
    for i in range(n):
        if not order:
            order = list(rng.permutation(initial))
        doc_id = int(order.pop())
        out.append({
            "index": i, "doc_id": doc_id,
            "text": f"q{i} " + corpus.question(rng, doc_id, int(qlens[i]) - 1),
        })
    return out


def arrival_times(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times of an open loop: the gaps are the quantiles of the
    exponential law at this rate (the same multiset for every seed, so the
    bursts are those of a Poisson process), in an order drawn from the
    seed."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    gaps = gaps[np.random.default_rng([int(seed), 37]).permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]


def upsert_plan(seed: int, mix: dict, seconds: float) -> list[tuple[float, str]]:
    """(due, kind) of each upsert of the window; kind is replace or new."""
    rate = float(mix.get("upserts_per_s", 0))
    if rate <= 0:
        return []
    n = int(math.floor(rate * seconds))
    rng = np.random.default_rng([int(seed), 41])
    n_replace = int(round(n * float(mix.get("replace_share", 0.5))))
    kinds = np.asarray(["replace"] * n_replace + ["new"] * (n - n_replace))
    kinds = kinds[rng.permutation(n)]
    return [((i + 0.5) / rate, str(k)) for i, k in enumerate(kinds)]
