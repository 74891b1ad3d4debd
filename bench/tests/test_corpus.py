"""The generator's corpus has the configuration's lengths, vocabulary and
Zipf head."""
import numpy as np

import corpus

CFG = {"num_docs": 3000, "vocab_size": 4000, "num_topics": 32,
       "alpha": 50 / 32, "beta": 0.01, "doc_len_median": 90,
       "doc_len_sigma": 0.6, "zipf_a": 1.1, "corpus_seed": 11}


def test_lengths_vocabulary_and_zipf_head():
    doc_ids, word_ids = corpus.generate(CFG)
    D, V = CFG["num_docs"], CFG["vocab_size"]
    lengths = np.bincount(doc_ids, minlength=D)
    assert lengths.size == D and lengths.min() >= 1
    assert np.array_equal(lengths, corpus.doc_lengths(CFG))
    mean = CFG["doc_len_median"] * np.exp(CFG["doc_len_sigma"] ** 2 / 2)
    assert abs(lengths.mean() / mean - 1) < 0.03
    assert word_ids.min() >= 0 and word_ids.max() < V
    freq = np.bincount(word_ids, minlength=V)
    assert (freq > 0).mean() > 0.5
    head = np.sort(freq)[::-1][:10] / word_ids.size
    want = corpus.expected_word_share(CFG)[:10]
    # topic draws scatter each word's share; the head's mass and slope hold
    assert abs(head.sum() / want.sum() - 1) < 0.35
    assert head[0] > 3 * head[9]


def test_same_config_same_corpus():
    a = corpus.generate(CFG)
    b = corpus.generate(CFG)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
