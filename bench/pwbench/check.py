"""The comparison that decides ``correct``: what the timed path served,
against the plain reference, once the window has closed.

Numbers compared (each printed beside its limit):

* ``logit_gap``: over a sample of finished answers, drawn from the seed
  and with the longest prompt in it, the widest gap by which a served
  token's reference logit lies below the reference's best at that
  position. The prompt is rebuilt from the context passages the reply
  itself returned, so the number covers prefill and decode through the
  slot cache at the timed widths; the sample holds an answer of every
  slot.
* ``rank_gap``: over the same sample (or a sample of retrieves), the
  widest gap by which a returned passage's reference similarity lies below
  the reference's k-th best over the live corpus. It covers encode (the
  Pallas kernel at the timed buckets) and the exact search of the slab.
* ``not_found``: documents the store reports as indexed whose own text,
  searched after the window, did not come back first; ``zombies``:
  retracted versions that came back. Both exact, limit 0.
* ``malformed``: replies of the window that are not what the route
  promises (wrong count of tokens or passages, unknown text). Limit 0.
"""

from __future__ import annotations

import re
from typing import Any

import numpy as np

from . import reference, spec, traffic, weights

_TOKEN = re.compile(r"<(\d+)>")


def served_tokens(response: str) -> list[int]:
    return [int(t) for t in _TOKEN.findall(response)]


def pick_sample(seed: int, candidates: list[Any], n: int) -> list[Any]:
    """n of the candidates, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 53])
    return [candidates[int(i)] for i in rng.permutation(len(candidates))[:n]]


def pick_per_slot(seed: int, candidates: list[dict], n_slots: int) -> list[dict]:
    """Finished answers drawn from the seed so that every slot of the
    batcher that served one is covered, the answer with the longest prompt
    among them. Where the program's slots are not known (``slot`` None),
    as many answers as there are slots."""
    if not candidates:
        return []
    rng = np.random.default_rng([int(seed), 53])
    order = [candidates[int(i)] for i in rng.permutation(len(candidates))]
    picked = [max(candidates, key=lambda c: c["prompt_tokens"])]
    seen = {picked[0]["slot"]}
    for c in order:
        if c["slot"] is None:
            if len(picked) < n_slots and c is not picked[0]:
                picked.append(c)
        elif c["slot"] not in seen:
            seen.add(c["slot"])
            picked.append(c)
    return picked


def _decoder_rows(sample: list[dict]) -> tuple[list, list]:
    """Each sampled answer as the row the reference reads (the prompt and
    the tokens that were given after it) and the positions whose logits
    decide the answer's tokens."""
    rows = [s["prompt"] + s["given"][:-1] for s in sample]
    at = [
        range(len(s["prompt"]) - 1, len(s["prompt"]) - 1 + len(s["given"]))
        for s in sample
    ]
    return rows, at


def logit_gaps(seed: int, config: dict, sample: list[dict]) -> dict:
    """``sample``: dicts with ``prompt`` (token ids), ``given`` (the served
    ids, which every later position was conditioned on) and ``tokens`` (the
    ids judged: the served ones, or the control's). Returns the widest gap
    by which a judged token's reference logit lies below the reference's
    best at its position."""
    family = spec.family_of(config)
    sz = family.sizes(config)
    rows, at = _decoder_rows(sample)
    ref = family.decoder_logits(seed, sz, rows, at, sz["positions"])
    gaps = []
    for lg, s in zip(ref, sample):
        judged = np.asarray(s["tokens"])
        gaps.append(lg.max(axis=1) - lg[np.arange(len(judged)), judged])
    return {
        "gap": float(max(g.max() for g in gaps)),
        "tokens": int(sum(len(g) for g in gaps)),
        "off_best": int(sum((g > 0).sum() for g in gaps)),
    }


def _encoder_rows(config: dict, live: dict[int, str], sample: list[dict]):
    sz = weights.encoder_sizes(config["encoder"])
    ids = sorted(live)
    rows = [traffic.tokenize(live[i], sz["vocab"], sz["positions"]) for i in ids]
    rows += [traffic.tokenize(s["query"], sz["vocab"], sz["positions"]) for s in sample]
    return sz, ids, rows


def rank_gaps(seed: int, config: dict, live: dict[int, str],
              in_flux: set[int], sample: list[dict], history: dict) -> dict:
    """``live``: doc id -> text of the corpus as it stood for the sampled
    requests; ``in_flux``: ids that changed while some sampled request was
    open (they may or may not be returned and are left out of the k-th
    best). ``sample``: dicts with ``query`` and ``texts`` (the passages
    judged: as returned, or the control's). Returns the widest gap by which
    a judged passage's reference similarity lies below the k-th best."""
    sz, ids, rows = _encoder_rows(config, live, sample)
    emb = reference.encoder_embed(seed, sz, rows)
    sims = emb[len(ids):] @ emb[:len(ids)].T  # [n_sample, n_docs]
    col = {d: j for j, d in enumerate(ids)}
    stable = np.asarray([d not in in_flux for d in ids])
    worst, unknown = 0.0, 0
    for r, s in enumerate(sample):
        k = len(s["texts"])
        kth = np.sort(sims[r][stable])[-k] if stable.sum() >= k else -np.inf
        for text in s["texts"]:
            doc = history.get(text)
            if doc is None:
                unknown += 1
                continue
            j = col.get(doc[0])
            if j is None or doc[0] in in_flux or live[doc[0]] != text:
                continue  # a version in flux: allowed either way
            worst = max(worst, float(kth - sims[r][j]))
    return {"gap": max(worst, 0.0), "requests": len(sample), "unknown_texts": unknown}


def control_sample(seed: int, config: dict, live: dict[int, str],
                   in_flux: set[int], sample: list[dict]) -> list[dict]:
    """The control in the program's place: the same requests, with the
    passages the fp8 reference ranks first (among those not in flux) where
    the served ones were, and, at each position of the same prompt and
    served tokens, the token the fp8 reference puts first where the served
    one was. It need not decode: ``given`` stays the served tokens."""
    sz, ids, rows = _encoder_rows(config, live, sample)
    emb = reference.encoder_embed(seed, sz, rows, fp8=True)
    sims = emb[len(ids):] @ emb[:len(ids)].T
    sims[:, [d in in_flux for d in ids]] = -np.inf
    out = []
    for r, s in enumerate(sample):
        top = np.argsort(-sims[r])[:len(s["texts"])]
        out.append({**s, "texts": [live[ids[int(j)]] for j in top]})
    if "given" in sample[0]:
        family = spec.family_of(config)
        dsz = family.sizes(config)
        rows, at = _decoder_rows(sample)
        ctl = family.decoder_logits(
            seed, dsz, rows, at, dsz["positions"], fp8=True
        )
        for s, lg in zip(out, ctl):
            s["tokens"] = [int(t) for t in lg.argmax(axis=1)]
    return out
