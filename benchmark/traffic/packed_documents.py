"""Training rows: documents of heavy-tailed length packed into fixed sequences.

One general generator; a mix is a data file beside this one that names it
(``"generator": "packed_documents"``) and gives ``seq``, ``rows_per_step``
and the log-normal of the document lengths. A row is made from
``(seed, row id)`` alone, so the same seed gives the same rows whatever
block of the dataset a row lands in, and every step sees a fresh batch.
Each document opens with ``bos_id``; the rest of its tokens are uniform
over the vocabulary (the weights are random too: speed and agreement with
the reference need no more). Documents are cut at the sequence's end, as
a packer does. The program attends across document boundaries; the
reference does the same.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def document_lengths(rng: np.random.Generator, params: Dict[str, Any],
                     total: int) -> list:
    """Log-normal lengths, clipped, until they cover ``total`` tokens."""
    out, covered = [], 0
    mu = np.log(params["doc_len_median"])
    while covered < total:
        n = int(np.clip(rng.lognormal(mu, params["doc_len_sigma"]),
                        params["doc_len_min"], params["doc_len_max"]))
        out.append(n)
        covered += n
    return out


def row(row_id: int, *, params: Dict[str, Any], seed: int,
        vocab: int) -> np.ndarray:
    """``seq + 1`` tokens of packed documents."""
    total = params["seq"] + 1
    rng = np.random.default_rng([int(seed), int(row_id)])
    toks = rng.integers(2, vocab, size=total, dtype=np.int64)
    start = 0
    for n in document_lengths(rng, params, total):
        toks[start] = params["bos_id"]
        start += n
        if start >= total:
            break
    return toks.astype(np.int32)


def rows(table, *, params: Dict[str, Any], seed: int,
         vocab: int) -> Dict[str, np.ndarray]:
    """``map_batches`` function over ``ray_tpu.data.range``: row ids in,
    ``inputs`` and ``targets`` [n, seq] out."""
    toks = np.stack([row(i, params=params, seed=seed, vocab=vocab)
                     for i in np.asarray(table["id"]).tolist()])
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
