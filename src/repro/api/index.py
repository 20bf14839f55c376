"""`SuffixArrayIndex` — text + suffix array + lazy LCP, with queries.

One object subsumes the previous loose functions (`corpus_sa.CorpusSA`,
`count_occurrences`, `cross_doc_duplicates`, `lcp.ngram_counts`,
`repeated_substring_spans`) behind a single facade:

* `SuffixArrayIndex.build(text, options)` — one document;
* `SuffixArrayIndex.from_docs(docs, options)` — multi-document corpus with
  the sentinel-separator layout (doc i is terminated by a unique separator
  of value i placed BELOW the shifted data alphabet, so no suffix comparison
  ever crosses a document boundary);
* `count_batch` / `locate_batch` / `contains_batch` — the query engine:
  many patterns padded into one device buffer, all SA ranges resolved by
  a single jitted vectorised binary search (`repro.api.query`);
* `count` / `locate` — scalar conveniences, thin shims over a batch of
  one (the legacy numpy bisection loop survives as `_sa_range`, the
  reference/regression path);
* `ngram_stats(k)` — total and distinct k-grams fully inside documents;
* `duplicate_spans(min_len)` — merged repeated-substring spans (the Lee et
  al. 2022 dedup criterion);
* `cross_doc_duplicates(min_len)` — vectorised contamination check;
* `save` / `load` — persistence through `repro.api.store` (an
  `IndexStore` adds naming, staleness checks, and get-or-build on top).

Pattern semantics are explicit: values must lie in ``[0, sigma)`` (the
index's data alphabet — inferred from the text or declared via
``sigma=``); out-of-alphabet values raise `ValueError` instead of
silently never matching. The empty pattern is a prefix of every suffix,
so ``count([]) == n``; `locate([])` raises `ValueError` (n positions is
a result you enumerate with `numpy.arange`, not a locate call).

The LCP array is computed lazily on first use and cached.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..core.spans import span
from ..text.lcp import lcp_kasai, repeated_substring_spans
from .build import build_suffix_array
from .options import SAOptions
from .query import QueryBatch, batch_ranges, stage_batch


def longest_match_len(index, seq) -> int:
    """Length of the longest substring of ``seq`` that occurs in ``index``.

    Works against anything with ``contains_batch`` (monolithic
    `SuffixArrayIndex` or `repro.api.SegmentedIndex`). Feasibility is
    monotone in the length (a substring's prefixes occur wherever it
    does), so a binary search over lengths resolves the answer with
    O(log |seq|) batched containment queries — each one jitted call
    testing *every* window of the probed length at once. This is the
    overlap primitive behind the memorization probe and contamination
    reporting in `repro.data.pipeline`.

    Out-of-alphabet values in ``seq`` can never match, so they are masked
    out up front (windows containing them are skipped, not errors) —
    generated samples may legally contain tokens the corpus never used.

    Against an index with a minimum answerable pattern length (a sparse
    index's ``min_pattern_len == sample_rate``), the search floors at
    that length: matches shorter than the floor report 0 (the index
    cannot certify them), matches ≥ the floor are exact and identical to
    the dense answer — monotonicity makes the floored binary search
    sound.
    """
    seq = np.asarray(seq, np.int64).ravel()
    if len(seq) == 0 or index.n == 0:
        return 0
    ok = (seq >= 0) & (seq < max(index.sigma, 1))

    def feasible(m: int) -> bool:
        wins = np.lib.stride_tricks.sliding_window_view(seq, m)
        valid = np.flatnonzero(
            np.lib.stride_tricks.sliding_window_view(ok, m).all(axis=1))
        if not len(valid):
            return False
        return bool(np.any(index.contains_batch(list(wins[valid]))))

    floor = int(getattr(index, "min_pattern_len", 0))
    lo, hi = 0, len(seq)            # longest feasible is in [lo, hi]
    if floor > 1:
        if len(seq) < floor or not feasible(floor):
            return 0                # any true match is below the floor
        lo = floor
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if feasible(mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


def encode_docs(docs) -> tuple[np.ndarray, np.ndarray, int]:
    """Sentinel-separator corpus layout: data values are shifted up by
    n_docs and doc i is terminated by separator value i. Separators are
    (a) unique, so no suffix comparison crosses a document boundary, and
    (b) below the data alphabet, so separator suffixes cluster at the front
    of the SA where they are cheap to skip.

    Returns (text int64[N], doc_starts int64[n_docs], n_docs).
    """
    n_docs = len(docs)
    if n_docs == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 0
    parts, starts, off = [], [], 0
    for i, d in enumerate(docs):
        d = np.asarray(d, np.int64)
        if d.ndim != 1:
            raise ValueError(f"doc {i} must be 1-D, got shape {d.shape}")
        if len(d) and int(d.min()) < 0:
            raise ValueError(f"doc {i} has negative values")
        starts.append(off)
        parts.append(d + n_docs)
        parts.append(np.asarray([i], np.int64))
        off += len(d) + 1
    return (np.concatenate(parts), np.asarray(starts, np.int64), n_docs)


@dataclass(frozen=True)
class NgramStats:
    """k-gram statistics over the indexed corpus (separator-free windows)."""

    k: int
    total: int        # number of k-gram positions fully inside one document
    distinct: int     # number of distinct k-gram strings among those


class SuffixArrayIndex:
    """Queryable suffix-array index over one document or a corpus.

    Positions returned by `locate` / `duplicate_spans` are offsets into the
    *encoded* text (`self.text`); for a single-document index these equal
    raw text offsets. Use `doc_of` / `doc_offset` to map a position into
    (document, in-document offset) for multi-document indexes.
    """

    def __init__(self, text, sa, *, doc_starts=None, shift: int = 0,
                 options: SAOptions | None = None, lcp=None,
                 sigma: int | None = None):
        self.text = np.asarray(text, np.int64)
        self.sa = np.asarray(sa, np.int32)
        self._check_shapes()
        n = len(self.text)
        self.doc_starts = (np.asarray(doc_starts, np.int64)
                           if doc_starts is not None
                           else np.zeros(1 if n else 0, np.int64))
        self.shift = int(shift)
        self.options = options if options is not None else SAOptions()
        self._lcp = None if lcp is None else np.asarray(lcp, np.int64)
        self._sigma = None if sigma is None else int(sigma)
        self._device = None        # lazy (text, sa) device buffers

    def _check_shapes(self) -> None:
        """Text-vs-SA shape contract; `repro.sparse` relaxes it to n/s."""
        if self.sa.shape != self.text.shape:
            raise ValueError(f"sa shape {self.sa.shape} != text shape "
                             f"{self.text.shape}")

    #: shortest pattern this index answers exactly; 0 = no restriction.
    #: `repro.sparse.SparseSuffixArrayIndex` overrides with its rate, and
    #: `longest_match_len` / serving warmups floor their probes at it.
    min_pattern_len = 0

    # ----------------------------------------------------------- construct
    @classmethod
    def build(cls, text, options: SAOptions | None = None, *,
              sigma: int | None = None, **overrides) -> "SuffixArrayIndex":
        """Index a single document (no separators, positions = raw offsets).

        Construction goes through `build_suffix_array`, so it benefits from
        the compiled-builder cache: indexing many similar-length documents
        under one plan reuses all jitted computations (see docs/api.md).
        Pass ``sigma=`` to declare the alphabet size explicitly (pattern
        validation otherwise infers it from the text's maximum value)."""
        opts = options if options is not None else SAOptions()
        if overrides:
            opts = opts.replace(**overrides)
        if opts.sample_rate > 1 and cls is SuffixArrayIndex:
            # facade dispatch: a sampled plan builds the sparse subclass
            from ..sparse import SparseSuffixArrayIndex
            return SparseSuffixArrayIndex.build(text, opts, sigma=sigma)
        with span("facade"):
            text = np.asarray(text, np.int64)
            sa = build_suffix_array(text, opts)
            return cls(text, sa, shift=0, options=opts, sigma=sigma)

    @classmethod
    def from_docs(cls, docs, options: SAOptions | None = None, *,
                  sigma: int | None = None, **overrides) -> "SuffixArrayIndex":
        """Index a list of documents with the sentinel-separator layout."""
        opts = options if options is not None else SAOptions()
        if overrides:
            opts = opts.replace(**overrides)
        if opts.sample_rate > 1 and cls is SuffixArrayIndex:
            from ..sparse import SparseSuffixArrayIndex
            return SparseSuffixArrayIndex.from_docs(docs, opts, sigma=sigma)
        with span("facade"):
            with span("encode"):
                text, starts, n_docs = encode_docs(docs)
            sa = build_suffix_array(text, opts)
            return cls(text, sa, doc_starts=starts, shift=n_docs,
                       options=opts, sigma=sigma)

    # --------------------------------------------------------- persistence
    def save(self, path: str) -> str:
        """Persist this index at `path` (`repro.api.store.save_index`)."""
        from .store import save_index
        return save_index(path, self)

    @classmethod
    def load(cls, path: str, *, options: SAOptions | None = None
             ) -> "SuffixArrayIndex":
        """Restore an index saved by `save` — no rebuild, no LCP recompute.

        Pass ``options`` to reject an artifact whose construction plan
        fingerprint differs (`repro.api.store.StaleIndexError`)."""
        from .store import load_index
        return load_index(path, options=options)

    # ----------------------------------------------------------- structure
    @property
    def n(self) -> int:
        return len(self.text)

    @property
    def n_docs(self) -> int:
        return len(self.doc_starts)

    @property
    def sep_count(self) -> int:
        return self.shift          # one separator per document when encoded

    @property
    def sigma(self) -> int:
        """Data-alphabet size: patterns must use values in [0, sigma).

        Inferred as ``max data value + 1`` unless declared at construction
        (``sigma=``); 0 for an index with no data characters."""
        if self._sigma is None:
            data_max = int(self.text.max()) - self.shift if self.n else -1
            self._sigma = max(data_max + 1, 0)
        return self._sigma

    @property
    def lcp(self) -> np.ndarray:
        """LCP array (Kasai), computed on first access and cached."""
        if self._lcp is None:
            self._lcp = lcp_kasai(self.text, self.sa)
        return self._lcp

    @property
    def _doc_ends(self) -> np.ndarray:
        """End (exclusive, separator position) of each document's payload."""
        if self.shift == 0:
            return np.full(self.n_docs, self.n, np.int64)
        return np.flatnonzero(self.text < self.shift).astype(np.int64)

    def doc_of(self, pos):
        """Document index owning encoded position(s) `pos` (scalar or array).

        Positions must lie in [0, n); out-of-range values raise IndexError
        (they used to wrap around silently — on an empty index
        `doc_offset(0)` crashed on `doc_starts[-1]`, on a non-empty one a
        negative position was attributed to the last document). An empty
        position *array* is always valid and maps to an empty result."""
        pos_arr = np.asarray(pos)
        if pos_arr.size and (np.any(pos_arr < 0) or np.any(pos_arr >= self.n)):
            raise IndexError(
                f"position(s) out of range for index of length {self.n}")
        idx = np.searchsorted(self.doc_starts, pos_arr, side="right") - 1
        if np.isscalar(pos) or np.ndim(pos) == 0:
            return int(idx)
        return idx.astype(np.int64)

    def doc_offset(self, pos):
        """(doc, in-document offset) for encoded position(s) `pos`."""
        doc = self.doc_of(pos)
        return doc, np.asarray(pos) - self.doc_starts[doc]

    # ------------------------------------------------------------- queries
    def _encode_pattern(self, pattern) -> np.ndarray:
        """Validate + shift a raw pattern into the encoded alphabet.

        Values must lie in ``[0, sigma)``: negatives always raise, and
        values ≥ sigma raise too (they can never occur in the data, so a
        silent 0-count would hide caller bugs — and before this check an
        out-of-range token could alias a separator after the shift). The
        alphabet check is skipped on an empty index (sigma is vacuously 0
        there; every count is 0 anyway).
        """
        pat = np.asarray(pattern, np.int64).ravel()
        if len(pat):
            if int(pat.min()) < 0:
                raise ValueError("pattern values must be ≥ 0")
            if self.n and int(pat.max()) >= self.sigma:
                raise ValueError(
                    f"pattern value {int(pat.max())} outside the index "
                    f"alphabet [0, {self.sigma}) — out-of-alphabet queries "
                    f"are rejected rather than silently counted as 0")
        return pat + self.shift

    def _device_state(self):
        """Device-resident (text, sa) buffers for the batched query kernel,
        created on first use and cached for the life of the index."""
        if self._device is None:
            import jax.numpy as jnp
            if self.n and int(self.text.max()) >= np.iinfo(np.int32).max:
                raise NotImplementedError(
                    "batched queries need int32-representable symbols "
                    f"(max encoded value {int(self.text.max())})")
            self._device = (jnp.asarray(self.text.astype(np.int32)),
                            jnp.asarray(self.sa))
        return self._device

    def _suffix_cmp(self, starts: np.ndarray, pat: np.ndarray) -> np.ndarray:
        """Vectorised 3-way prefix compare of suffixes at `starts` vs `pat`:
        -1 suffix < pat, 0 pat is a prefix of suffix, +1 suffix > pat.
        One numpy gather + compare per call — no Python character loop."""
        starts = np.asarray(starts, np.int64).ravel()
        m, n = len(pat), self.n
        if m == 0 or n == 0:
            # empty pattern is a prefix of everything; on an empty index
            # every probe is past-the-end, i.e. "suffix < pat". Guarded
            # here so n-1 == -1 can never wrap the gather below.
            return np.full(len(starts), -1 if (n == 0 and m) else 0, np.int8)
        idx = starts[:, None] + np.arange(m, dtype=np.int64)[None, :]
        in_range = idx < n
        seg = np.where(in_range, self.text[np.minimum(idx, n - 1)],
                       np.int64(-1))       # past-the-end < every real char
        diff = seg != pat[None, :]
        any_diff = diff.any(axis=1)
        first = np.where(any_diff, diff.argmax(axis=1), 0)
        rows = np.arange(len(starts))
        out = np.zeros(len(starts), np.int8)
        s_at, p_at = seg[rows, first], pat[first]
        out[any_diff & (s_at < p_at)] = -1
        out[any_diff & (s_at > p_at)] = 1
        return out

    def _sa_range(self, pat: np.ndarray) -> tuple[int, int]:
        """[lo, hi) block of SA ranks whose suffixes start with `pat`.

        The *scalar reference* search: a Python binary-search loop where
        every probe is one vectorised `_suffix_cmp` call → O(|pat| log n)
        numpy work per pattern. Serving traffic goes through the batched
        jitted path instead (`sa_ranges_batch`); this loop is kept as the
        equivalence oracle for `tests/api/test_query.py` and the
        regression row of `benchmarks/query_throughput.py`."""
        n = len(self.sa)
        if len(pat) == 0:
            return 0, n
        lo = np.zeros(2, np.int64)
        hi = np.full(2, n, np.int64)
        while True:
            active = lo < hi
            if not active.any():
                break
            mid = (lo + hi) // 2
            c = self._suffix_cmp(self.sa[np.where(active, mid, 0)], pat)
            # bound 0 = first suffix ≥ pat, bound 1 = first suffix > pat
            before = np.array([c[0] < 0, c[1] <= 0])
            lo = np.where(active & before, mid + 1, lo)
            hi = np.where(active & ~before, mid, hi)
        return int(lo[0]), int(lo[1])

    # ------------------------------------------------------ batched queries
    def _as_batch(self, patterns) -> QueryBatch:
        return (patterns if isinstance(patterns, QueryBatch)
                else QueryBatch.encode(self, patterns))

    def sa_ranges_batch(self, patterns) -> tuple[np.ndarray, np.ndarray]:
        """`[lo, hi)` SA-rank ranges for many patterns in ONE device call.

        `patterns` is a sequence of int sequences (mixed lengths fine) or
        a pre-encoded `QueryBatch` for reuse. Returns two int64 arrays of
        length `len(patterns)`. Empty patterns resolve to (0, n); patterns
        longer than the text to an empty range."""
        return batch_ranges(self, self._as_batch(patterns))

    def count_batch(self, patterns) -> np.ndarray:
        """Occurrence counts for many patterns — int64[len(patterns)],
        resolved by one jitted vectorised binary search. The empty pattern
        is a prefix of every suffix, so it counts n."""
        lo, hi = self.sa_ranges_batch(patterns)
        return hi - lo

    def contains_batch(self, patterns) -> np.ndarray:
        """Presence flags for many patterns — bool[len(patterns)]."""
        return self.count_batch(patterns) > 0

    def locate_batch(self, patterns) -> list:
        """Sorted encoded start positions per pattern — a list of int64
        arrays. Raises `ValueError` on an empty pattern (its result is
        "every position"; enumerate that with `numpy.arange(n)`)."""
        qb = self._as_batch(patterns)
        if self.n and np.any(qb.lens[:qb.n_queries] == 0):
            raise ValueError("locate of an empty pattern is every position "
                             "in the index; use numpy.arange(n) instead")
        lo, hi = batch_ranges(self, qb)
        return [np.sort(self.sa[l:h].astype(np.int64))
                for l, h in zip(lo, hi)]

    def locate_docs_batch(self, patterns) -> list:
        """Occurrences in **document coordinates**: one int64[k, 2] array
        of (doc, in-doc offset) rows per pattern, sorted
        lexicographically. This is the representation shared with
        `repro.api.SegmentedIndex.locate_batch` — the segment-merge
        property tests compare the two byte-for-byte (encoded positions
        are ascending exactly when (doc, offset) rows are lex-sorted,
        since doc_starts is increasing)."""
        out = []
        for pos in self.locate_batch(patterns):
            doc, off = self.doc_offset(pos)
            out.append(np.stack([np.asarray(doc, np.int64).ravel(),
                                 np.asarray(off, np.int64).ravel()], axis=1)
                       if len(pos) else np.zeros((0, 2), np.int64))
        return out

    # --------------------------------------------------- encoded fan-in API
    def _counts_encoded(self, enc) -> np.ndarray:
        """Counts for already-encoded patterns (`_encode_pattern` output).

        The uniform per-segment primitive `repro.api.SegmentedIndex` fans
        out over — encoded once globally, shift-adjusted per segment —
        implemented by every index flavour (the sparse subclass resolves
        it through its two-level plan instead of SA ranges)."""
        lo, hi = batch_ranges(self, QueryBatch.from_encoded(self, enc))
        return hi - lo

    def _positions_encoded(self, enc) -> list:
        """Sorted encoded positions per already-encoded pattern — the
        locate-side companion of `_counts_encoded`."""
        lo, hi = batch_ranges(self, QueryBatch.from_encoded(self, enc))
        return [np.sort(self.sa[l:h].astype(np.int64))
                for l, h in zip(lo, hi)]

    # ------------------------------------------------- serving-tier protocol
    def stage_encoded(self, enc):
        """Package already-encoded patterns (`_encode_pattern` output) for
        the serving tier and begin their host→device transfer. Returns an
        opaque work item for `ranges_staged` — `repro.serve.SAServer`
        double-buffers the pair, and `SegmentedIndex` implements the same
        two methods with a per-segment fan-out inside."""
        batch = QueryBatch.from_encoded(self, enc)
        return (batch, stage_batch(self, batch) if self.n else None)

    def ranges_staged(self, work) -> tuple[np.ndarray, np.ndarray]:
        """Resolve a `stage_encoded` work item to its (lo, hi) SA ranges."""
        batch, staged = work
        return batch_ranges(self, batch, staged=staged)

    # ----------------------------------------------------- scalar shims
    def count(self, pattern) -> int:
        """Occurrences of `pattern` across the corpus.

        Thin shim over a batch of one (`count_batch`); `count([]) == n`
        by the empty-prefix rule."""
        return int(self.count_batch([pattern])[0])

    def locate(self, pattern) -> np.ndarray:
        """Sorted encoded start positions of every occurrence of `pattern`.
        Thin shim over a batch of one (`locate_batch`)."""
        return self.locate_batch([pattern])[0]

    def locate_docs(self, pattern) -> np.ndarray:
        """Occurrences as an int64[k, 2] array of (doc, in-doc offset)."""
        pos = self.locate(pattern)
        doc, off = self.doc_offset(pos)
        return np.stack([np.asarray(doc, np.int64), off], axis=1)

    def longest_match(self, seq) -> int:
        """Longest substring of ``seq`` occurring anywhere in the index
        (`longest_match_len`) — the memorization-probe primitive."""
        return longest_match_len(self, seq)

    # ---------------------------------------------------------- statistics
    def ngram_stats(self, k: int) -> NgramStats:
        """Total / distinct k-grams, counting only windows that lie fully
        inside one document (never spanning a separator)."""
        if k <= 0 or self.n == 0:
            return NgramStats(k=k, total=0, distinct=0)
        pos = self.sa.astype(np.int64)
        if self.shift == 0:
            valid = pos + k <= self.n
        else:
            ends = self._doc_ends
            owner = np.searchsorted(self.doc_starts, pos, side="right") - 1
            valid = pos + k <= ends[owner]
        distinct = int(np.sum(valid & (self.lcp < k)))
        return NgramStats(k=k, total=int(np.sum(valid)), distinct=distinct)

    def duplicate_spans(self, min_len: int) -> list:
        """Merged (start, end) spans covered by a substring of length ≥
        min_len occurring at least twice (Lee et al. dedup criterion).
        Separator uniqueness guarantees spans never cross documents."""
        return repeated_substring_spans(self.text, self.sa, self.lcp, min_len)

    def cross_doc_duplicates(self, min_len: int) -> list:
        """(doc_i, doc_j, length) for SA-adjacent repeats ≥ min_len spanning
        two DIFFERENT documents — fully vectorised (mask over lcp ≥ min_len
        + batched searchsorted doc lookup)."""
        lcp = self.lcp
        r = np.flatnonzero(lcp >= min_len)
        r = r[r >= 1]
        if len(r) == 0:
            return []
        a = self.sa[r - 1].astype(np.int64)
        b = self.sa[r].astype(np.int64)
        da = np.searchsorted(self.doc_starts, a, side="right") - 1
        db = np.searchsorted(self.doc_starts, b, side="right") - 1
        hit = da != db
        lo = np.minimum(da, db)[hit]
        hi = np.maximum(da, db)[hit]
        ln = lcp[r][hit]
        return [(int(i), int(j), int(l)) for i, j, l in zip(lo, hi, ln)]

    def __repr__(self) -> str:
        return (f"SuffixArrayIndex(n={self.n}, n_docs={self.n_docs}, "
                f"backend={self.options.resolve_backend()!r}, "
                f"lcp={'cached' if self._lcp is not None else 'lazy'})")
