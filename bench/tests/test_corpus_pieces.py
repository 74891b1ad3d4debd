"""The corpus generator in bounded pieces.  The corpora it made before
pieces existed stay bit-identical; the pieced path keeps the generative
process; at the vocabulary widths of the paper's Amazon and UMBC corpora
no index overflows int32, no array outgrows a piece, and every program
fits a v5e."""
import hashlib
import json
import os
import re
from pathlib import Path

import numpy as np
import pytest

import corpus
from test_corpus import CFG

CONFIGS = Path(__file__).resolve().parent / "data" / "configs"

# sha256 of (doc_ids, word_ids) from the generator as it was before
# pieces existed, on the CPU
PINS = {
    "tiny": ("77ec488c4becd5d26163ea46090b0c4337914f38d80bfc95e0a4e13a0454aa70",
             "45c4844e10f0cbfbe77377cad17f79ab4e6b007185847613839f688127c8c4e5"),
    "tiny4": ("c6b1cdae0a6b156e9175e7acb473065719d634fd0a91a10d2fe8ca840266e139",
              "3ff6f3d09397132ba2c4be359d20cb08f589d10ae1e0dbf6ddd6af6d32fae7ed"),
    "test_corpus.CFG": (
        "218619d58df0269dca164acdd2906c135bb51e585f8c36968f0bb35ad9e21ca7",
        "aa6ae06a612ddfceaba6032f668a607d9834f9b3d96d9c646d5638ee43df8b0f"),
}

# 1,400 documents in 3 pieces of 500, 4,000 words in 4 slices of 1,000
PIECED = dict(CFG, num_docs=1400, num_topics=128, alpha=50 / 128)
SMALL_PIECE = 64 * 1000

# (documents, tokens, vocabulary) at T = 1024: the paper's Amazon and UMBC
# widths, documents cut to ≈ 8.2M tokens
WIDE = {"amazon": (163_840, 8_200_000, 1_682_527),
        "umbc": (221_184, 8_200_000, 2_881_476)}
WIDE_T = 1024
LIMIT_BYTES = 13 * 10**9     # of a v5e's 15.75 GB, room for the runtime


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a, np.int32).tobytes()
                          ).hexdigest()


@pytest.mark.parametrize("name", sorted(PINS))
def test_corpus_is_pinned(name):
    cfg = CFG if name == "test_corpus.CFG" else json.loads(
        (CONFIGS / f"{name}.json").read_text())
    doc_ids, word_ids = corpus.generate(cfg)
    assert (sha(doc_ids), sha(word_ids)) == PINS[name]


def test_one_piece_streamed_is_the_whole_draw():
    """Streamed in one piece, the draws, keys and words are those of the
    program that holds φ's whole CDF."""
    lengths = corpus.doc_lengths(PIECED)
    D, N = lengths.size, int(lengths.sum())
    sizes = (D, N, PIECED["vocab_size"], PIECED["num_topics"],
             PIECED["alpha"], PIECED["beta"], PIECED["zipf_a"], 64)
    import jax
    import jax.numpy as jnp
    key = jax.random.key(PIECED["corpus_seed"])
    doc_ids = jnp.asarray(np.repeat(np.arange(D, dtype=np.int32), lengths))
    whole = corpus._sampler(*sizes)(key, doc_ids)
    streamed = corpus._streamed(*sizes, 1, 1)(key, doc_ids)
    assert np.array_equal(np.asarray(whole), np.asarray(streamed))


@pytest.fixture(scope="module")
def pieced():
    return corpus._generate(PIECED, SMALL_PIECE)


def test_pieced_lengths_and_range(pieced):
    lengths = corpus.doc_lengths(PIECED)
    assert corpus._pieces(lengths.size, PIECED["vocab_size"],
                          PIECED["num_topics"], SMALL_PIECE) == (64, 3, 4)
    doc_ids, word_ids = pieced
    assert np.array_equal(np.bincount(doc_ids, minlength=lengths.size),
                          lengths)
    assert word_ids.min() >= 0 and word_ids.max() < PIECED["vocab_size"]


def test_pieced_zipf_head(pieced):
    _, word_ids = pieced
    freq = np.bincount(word_ids, minlength=PIECED["vocab_size"])
    head = np.sort(freq)[::-1][:10] / word_ids.size
    want = corpus.expected_word_share(PIECED)[:10]
    assert abs(head.sum() / want.sum() - 1) < 0.35
    assert head[0] > 3 * head[9]


def test_pieced_slice_shares(pieced):
    """Each vocabulary slice takes the share of tokens that its words'
    concentration predicts (the slice masses' mean)."""
    import jax
    _, word_ids = pieced
    V, S = PIECED["vocab_size"], 4
    k_perm = jax.random.split(jax.random.key(PIECED["corpus_seed"]), 5)[0]
    conc = np.asarray(corpus._concentration(
        k_perm, V, PIECED["beta"], PIECED["zipf_a"]), np.float64)
    want = conc.reshape(S, -1).sum(1) / conc.sum()
    got = np.bincount(word_ids // (V // S), minlength=S) / word_ids.size
    assert np.all(np.abs(got / want - 1) < 0.35), (got, want)
    # and each word its own share, so no slice's words land on another's
    freq = np.bincount(word_ids, minlength=V) / word_ids.size
    assert np.corrcoef(freq, conc / conc.sum())[0, 1] > 0.95


def _lowered(name, sharding=None):
    import jax
    import jax.numpy as jnp
    D, N, V = WIDE[name]
    prog = corpus._program(D, N, V, WIDE_T, 50 / WIDE_T, 0.01, 1.1)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=sharding)
    return prog.lower(key, jax.ShapeDtypeStruct((N,), jnp.int32,
                                                sharding=sharding))


@pytest.mark.parametrize("name", sorted(WIDE))
def test_wide_vocabulary_lowers_within_a_piece(name):
    """Tracing at the width raises no int32 overflow; no array of the
    program has ``T × V`` elements, and no float array more than a piece
    (the gamma sampler's key arrays carry a few key words an element)."""
    text = _lowered(name).as_text()
    sizes = {}
    for dims, dtype in re.findall(r"tensor<((?:\d+x)+)([a-z]+)", text):
        n = int(np.prod([int(d) for d in dims.rstrip("x").split("x")]))
        sizes[dtype] = max(sizes.get(dtype, 0), n)
    assert max(sizes.values()) < WIDE_T * WIDE[name][2]
    assert sizes["f"] <= corpus.PIECE


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


@pytest.mark.parametrize("name", sorted(WIDE))
def test_wide_vocabulary_fits_a_v5e(v5e, name):
    m = _lowered(name, v5e).compile().memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used <= LIMIT_BYTES, f"{used / 1e9:.2f} GB"
