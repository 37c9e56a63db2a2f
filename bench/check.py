"""What decides ``correct``: the answers of the window against plain
references that import nothing of the program.

* Engine answers: a sample drawn from the seed of the window's engine-decoded
  requests, the longest among them, is run through the float32 reference
  (``bench/model.py``) over each prompt the engine saw and the tokens it
  served.  ``logit_gap`` is the widest gap by which a served token's
  reference logit lies below the reference's best: greedy decoding in the
  served bfloat16 only loses near-ties, a wrong page, mask, kernel or
  token loses far more.
* The prompt the engine saw is read from the prefill calls (their tokens
  and start positions) and must be the tail of the reference's own byte
  tokenization of the request's last user message (``unmatched``).
* Cache answers: every answer the semantic cache served is checked against
  an exact cosine search over all cached questions, embedded by the
  reference's own copy of the hashed bag-of-words embedding
  (``cache_wrong``): the answer must be the object the search names, the
  object must clear the relevance threshold, and a repeated question must
  get its own object (or one that scores as high).
* The wire: the text the client assembled (streamed deltas concatenated)
  must equal the text the bridge produced, which must equal the byte
  decoding of the served tokens (``wire_mismatch``).
* ``lost``: requests due in the window that never got an answer, not even a
  refusal, by the deadline.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Tuple

import numpy as np

N_SPECIAL = 3          # pad, bos, eos come before the 256 byte ids
BOS = 1


def byte_ids(text: str) -> List[int]:
    return [BOS] + [b + N_SPECIAL for b in text.encode("utf-8")]


def byte_text(ids) -> str:
    """Ids above the byte range fold back into it, as the served decode
    does for random-weight models whose vocabulary is larger."""
    return bytes((int(i) - N_SPECIAL) % 256 for i in ids
                 if int(i) >= N_SPECIAL).decode("utf-8", errors="replace")


def bow(text: str, dim: int) -> np.ndarray:
    """Hashed bag of words, unit norm: the semantic cache's embedding of
    text it holds no planted vector for."""
    v = np.zeros(dim, np.float64)
    for w in text.lower().split():
        h = hashlib.blake2b(w.encode(), digest_size=8).digest()
        v[int.from_bytes(h[:4], "little") % dim] += 1.0 if h[4] % 2 else -1.0
    n = np.linalg.norm(v)
    return v / n if n > 0 else v


def lost(records) -> int:
    """No answer and no refusal: no status, or a 200 stream that ended in
    neither [DONE] nor an error frame."""
    return sum(1 for r in records
               if r["status"] is None
               or (r["status"] == 200 and not r["done"] and not r["error"]))


def wire_mismatch(records, served) -> int:
    bad = 0
    for r in records:
        if not r["ok"]:
            continue
        s = served.get(r["rid"])
        if s is None or s.get("text") != r["text"]:
            bad += 1
        elif not r["cache_hit"] and byte_text(s["tokens"]) != s["text"]:
            bad += 1
    return bad


def prefill_index(calls) -> Dict[Tuple[int, ...], int]:
    """Prompt tails the engine prefilled -> their start position.  Calls
    with more than one token per row are prefills; right padding is 0."""
    out: Dict[Tuple[int, ...], int] = {}
    for _, tokens, positions in calls:
        if tokens.shape[1] == 1:
            continue
        toks, pos = np.asarray(tokens), np.asarray(positions)
        for row, p in zip(toks, pos):
            n = len(row)
            while n and row[n - 1] == 0:
                n -= 1
            if n:
                out[tuple(int(t) for t in row[:n])] = int(p[0])
    return out


def engine_prompt(prompt: str, index) -> List[int]:
    """The prompt window the engine conditioned on: the longest tail of the
    reference tokenization that the engine prefilled, extended by the
    prefill's start (tokens it took from shared pages)."""
    ids = byte_ids(prompt)
    for k in range(len(ids), 0, -1):
        start = index.get(tuple(ids[-k:]))
        if start is not None:
            return ids[-(k + start):] if k + start <= len(ids) else []
    return []


def sample(records, served, seed: int, n: int):
    """The engine-decoded requests compared: the longest, then a draw from
    the seed."""
    eng = [r for r in records if r["ok"] and not r["cache_hit"]
           and served.get(r["rid"], {}).get("tokens")]
    if not eng:
        return []
    eng.sort(key=lambda r: (-len(served[r["rid"]]["tokens"]), r["i"]))
    rest = eng[1:]
    rng = np.random.default_rng([seed, 9])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [eng[0]] + [rest[int(j)] for j in sorted(pick)]


def cache_wrong(records, requests_by_i, objects, dim: int,
                threshold: float, model_name: str) -> Tuple[int, int]:
    """(wrong, checked) over every cache-served answer of the window."""
    hits = [r for r in records if r["ok"] and r["cache_hit"]]
    if not hits:
        return 0, 0
    keys = np.stack([bow(o["question"], dim) for o in objects])
    by_prefix = {o["answer"].split(" ", 1)[0]: o["id"] for o in objects}
    head = f"[{model_name}+cache] "
    wrong = 0
    for r in hits:
        req = requests_by_i[r["i"]]
        scores = keys @ bow(req.prompt, dim)
        text = r["text"]
        k = by_prefix.get(text[len(head):].split(" ", 1)[0]) \
            if text.startswith(head) else None
        if k is None or text != head + objects[k]["answer"][:96]:
            wrong += 1
        elif scores[k] < threshold - 1e-5 or scores[k] < scores.max() - 1e-5:
            # a served object must clear the threshold and be the best the
            # exact search finds, up to ties
            if req.repeat_of is not None or scores[k] < threshold - 1e-5:
                wrong += 1
    return wrong, len(hits)


def logit_gap(params, config, rows, fp8: bool = False) -> float:
    from bench import model
    if not rows:
        return 0.0
    gaps = np.concatenate([model.token_gaps(params, rows[i:i + 4], config,
                                            fp8=fp8)
                           for i in range(0, len(rows), 4)])
    return float(gaps.max())


def engine_rows(picked, requests_by_i, served, index, vocab: int):
    """(prompt ids, served ids) of each sampled request, and how many
    could not be matched to a prompt the engine saw."""
    rows, unmatched = [], 0
    for r in picked:
        toks = served[r["rid"]]["tokens"]
        p = engine_prompt(requests_by_i[r["i"]].prompt, index)
        if not p or any(not 0 <= t < vocab for t in toks):
            unmatched += 1
            continue
        rows.append((p, toks))
    return rows, unmatched


def decide(numbers: Dict[str, Dict[str, Any]]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
