"""The work counts depend on T alone and keep every share under 100%."""
import inspect
import json
from pathlib import Path

import pytest

import work

BENCH = Path(__file__).resolve().parents[1]


def test_counts_take_only_T():
    for fn in (work.ops_per_token, work.bytes_per_token):
        assert list(inspect.signature(fn).parameters) == ["T"]
    assert work.ops_per_token(1024) == 7 * 1024 + 7
    assert work.bytes_per_token(1024) == 12 * 1024 + 64


@pytest.mark.parametrize("cfg", ["nytimes-t1024", "pubmed-t1024"])
def test_same_T_same_work(cfg):
    c = json.loads((BENCH / "configs" / f"{cfg}.json").read_text())
    T = c["num_topics"]
    assert work.ops_per_token(T) == work.ops_per_token(1024)
    assert work.bytes_per_token(T) == work.bytes_per_token(1024)


@pytest.mark.parametrize("tokens_per_s", [12_000, 235_002, 2_350_020])
def test_shares_stay_under_100(tokens_per_s):
    """At the smoke's scan and fused rates, and at ten times the fused
    rate, the least time of a second's tokens is under a second."""
    import run
    peak = run.load_module(BENCH / "trace.py").peak("TPU v5 lite")
    least, bound = work.least_seconds(1024, tokens_per_s, peak)
    assert bound == "bytes"
    assert 0 < least < 1.0
    assert 100 * tokens_per_s * work.ops_per_token(1024) / peak["flops"] < 100
