from repro.data.synth import (
    StreamedCorpus,
    SynthCorpus,
    make_corpus,
    make_queries,
    make_streamed_corpus,
)

__all__ = [
    "StreamedCorpus",
    "SynthCorpus",
    "make_corpus",
    "make_queries",
    "make_streamed_corpus",
]
