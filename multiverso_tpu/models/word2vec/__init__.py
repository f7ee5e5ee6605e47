from multiverso_tpu.models.word2vec.data import (BatchGenerator, BlockStream,
                                                 CbowBatch, SkipGramBatch,
                                                 read_corpus)
from multiverso_tpu.models.word2vec.dictionary import (Dictionary,
                                                       HuffmanEncoder,
                                                       Sampler)
from multiverso_tpu.models.word2vec.model import (
    DISPATCH_MODES, Word2Vec, Word2VecConfig, measured_dispatch_latency_ms,
    resolve_dispatch_mode)

__all__ = ["Word2Vec", "Word2VecConfig", "Dictionary", "HuffmanEncoder",
           "Sampler", "BatchGenerator", "BlockStream", "SkipGramBatch",
           "CbowBatch", "read_corpus", "DISPATCH_MODES",
           "measured_dispatch_latency_ms", "resolve_dispatch_mode"]
