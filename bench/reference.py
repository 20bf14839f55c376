"""The plain reference that decides `correct`.

Nothing here imports the program or takes anything it made: the encoded
text is rebuilt from the documents by the index's documented layout, and
the suffix array is checked in linear time without an oracle.
"""
from __future__ import annotations

import numpy as np


def encoded_text(docs) -> np.ndarray:
    """The text a corpus index is built over: every token shifted up by
    the number of documents, and document i followed by the separator i
    (unique, and below every token)."""
    k = len(docs)
    parts = []
    for i, d in enumerate(docs):
        parts.append(np.asarray(d, np.int64) + k)
        parts.append(np.full(1, i, np.int64))
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def suffix_array_faults(text, sa) -> int:
    """How many entries keep `sa` from being the suffix array of `text`;
    0 means it is exactly the suffix array.

    Linear time, no oracle: `sa` must be a permutation of the positions,
    and each adjacent pair must be ordered by its first character and, on
    a tie, by the rank of the next suffix (the empty suffix ranks lowest).
    Together these hold for the suffix array and for nothing else."""
    text = np.asarray(text, np.int64)
    sa = np.asarray(sa, np.int64).ravel()
    n = len(text)
    if sa.shape != (n,):
        return max(n, len(sa))
    inside = (sa >= 0) & (sa < n)
    seen = np.zeros(n, bool)
    seen[sa[inside]] = True
    missing = int(n - np.count_nonzero(seen))
    if missing or not inside.all():
        return max(missing, int(np.count_nonzero(~inside)), 1)
    rank = np.empty(n + 1, np.int64)
    rank[sa] = np.arange(n)
    rank[n] = -1
    a, b = sa[:-1], sa[1:]
    ordered = (text[a] < text[b]) | ((text[a] == text[b])
                                     & (rank[a + 1] < rank[b + 1]))
    return int(np.count_nonzero(~ordered))

