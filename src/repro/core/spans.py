"""Host spans of a suffix-array build, in the JAX profiler's trace.

`span(name, **counts)` is a `jax.profiler.TraceAnnotation` named
``"sa." + name``: while the profiler records, it lands in the same
``.xplane.pb`` as the device planes, on the same clock, so a device idle
gap is named by the span open across it; otherwise it costs a couple of
microseconds. Counters are scalars the code already holds at the
boundary, passed as keyword arguments or, where they are known only inside
the span, set on it with ``set_metadata``; each is one that a reading of
the trace uses. Spans are opened on the host only, never inside a jitted
function.

Every span and its counters, nested as listed:

``sa.facade``
    `SuffixArrayIndex.from_docs` / `.build`: encoding, validation, casts,
    the backend call and the index object.
``sa.encode``
    `encode_docs`: the sentinel-separator layout of a corpus.
``sa.dcv.level`` (``level``, ``n_v``, ``v``)
    One DC-v recursion level of `suffix_array_jax`; it holds the next
    level's span. ``level`` is 0 at the top, ``n_v`` the padded window
    rows and ``v`` the cover modulus.
``sa.dcv.pack``
    Two a level: padding and the level constants, then (not on
    ``bitonic``) the window keys: uint32 lanes (``lax``), packed uint64
    words (``radix``), columns (``pallas``).
``sa.dcv.sort``
    A window sort up to the order in host memory. ``lax``: upload, the
    `lsd_argsort` program and the download; ``radix``: the MSD word sort,
    which yields the run boundaries too; ``pallas`` and ``bitonic``: the
    device sort (``bitonic`` has two a level, the sample sort and the
    final one).
``sa.dcv.runs``
    Run boundaries along the order (not on ``radix`` or ``bitonic``).
``sa.dcv.rank`` (``distinct``)
    Step 1: the order filtered to the samples, their ranks and the
    scatter into the next level's text (or straight into sample ranks
    when ``distinct`` is 1 and the recursion stops).
``sa.dcv.ties``
    Steps 2-4: the sample-rank array and tie resolution over the window
    order.
``sa.dcv.refine`` (``ties``)
    One stride-doubling round, while the tie set is large; ``ties`` rows
    are tied as it starts.
``sa.dcv.lemma1`` (``ties``, ``width``, ``path``, ``slots``)
    The Lemma-1 comparator on the residue: ``ties`` rows whose widest
    group needs ``width`` lanes, on the ``host`` (numpy) or the ``device``
    (jitted network, upload and download included). ``slots``, on the
    host only: the lanes each stage evaluates, every group padded to its
    own power-of-two width (``ties / slots`` is the share doing real
    work).
``sa.dcv.base``
    The recursion's base case, a prefix-doubling sort (on the device
    except for ``radix``).
"""
from __future__ import annotations

import jax

PREFIX = "sa."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """The host span ``"sa." + name`` with `counts` as its arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
