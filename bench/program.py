"""The system under test: the only call the benchmark makes into the
program. Controls and planted faults replace this object, never the
driver around it."""
from __future__ import annotations


class Builder:
    """Index one corpus shard; hand back its suffix array."""

    def build(self, docs):
        from repro.api import SAOptions, SuffixArrayIndex
        return SuffixArrayIndex.from_docs(docs, SAOptions()).sa
