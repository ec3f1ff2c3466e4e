"""Tests of who may answer a request: ``reduce.held_to_its_sources`` over
hand-made counter samples, for configurations that name the device alone
and for one that names the dedup tiers. CPU only:

    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import reduce  # noqa: E402

SOURCES = reduce.answer_sources()
DEVICE_ALONE = {"guarantees": {"answers_from": {
    "device": "policy_server_dispatched_rows"}}}
CACHED = json.loads((BENCH / "configs" / "flagship32-cached.json").read_text())
# what the parent's run.py summed under answered_off_device (46b6308..217edeb)
PARENTS_MUST_STAY_ZERO = (
    "policy_server_host_fastpath_requests",
    "policy_server_oracle_fallbacks",
    "policy_server_breaker_trips",
    "policy_server_breaker_short_circuited_requests",
    "policy_server_deadline_abandoned_batches",
)


def _samples(moved: dict[str, float], base: float = 100.0) -> tuple:
    """/metrics before and after a window in which each named source's
    counter (and ``fragment_hits``, no source) moved by the given count;
    every other source's counter is there and stands still."""
    counters = {spec["counter"]: 0 for spec in SOURCES.values()}
    counters["policy_server_fragment_hits"] = 0
    for source, n in moved.items():
        counters[SOURCES[source]["counter"] if source in SOURCES else source] = n

    def text(at: dict) -> str:
        return "".join(f"{name}_total {value}\n" for name, value in at.items())

    return (reduce.parse_metrics(text(dict.fromkeys(counters, base))),
            reduce.parse_metrics(text({k: base + n for k, n in counters.items()})))


@pytest.mark.parametrize("config, moved, answers, want", [
    pytest.param(DEVICE_ALONE, {"device": 30_000}, 30_000, (0, 0),
                 id="device alone, every row dispatched"),
    pytest.param(CACHED, {"device": 3_900, "row_tier": 24_100,
                          "blob_tier": 500, "batch_duplicate": 1_500},
                 30_000, (0, 0), id="the named tiers' hits close the count"),
    pytest.param(DEVICE_ALONE, {"device": 29_999, "row_tier": 1}, 30_000,
                 (1, 1), id="an unnamed tier's hit is answered_off_device"),
    pytest.param(CACHED, {"device": 3_900, "row_tier": 24_000,
                          "batch_duplicate": 1_500}, 30_000, (0, 600),
                 id="a named tier that counts too few leaves rows over"),
    pytest.param(CACHED, {"device": 3_900, "row_tier": 26_100,
                          "host_fastpath": 7}, 30_007, (7, 7),
                 id="the host fast path stays unnamed with the cache on"),
    pytest.param(CACHED, {"device": 30_000, "row_tier": 30_000}, 30_000,
                 (0, 30_000), id="an answer two tiers both count is not closed"),
    pytest.param(CACHED, {"device": 3_900, "row_tier": 26_100,
                          "policy_server_fragment_hits": 26_100}, 30_000,
                 (0, 0), id="fragment_hits moving changes nothing"),
    pytest.param(DEVICE_ALONE, {"device": 30_000, "breaker_trip": 1,
                                "deadline_abandoned": 2, "matrix_lookup": 3,
                                "degraded": 4}, 30_000, (10, 0),
                 id="every source nobody names must stay 0"),
])
def test_a_configuration_is_held_to_the_sources_it_names(
        config, moved, answers, want):
    before, after = _samples(moved)
    got = reduce.held_to_its_sources(config, before, after, answers)
    assert (got["answered_off_device"], got["rows_not_dispatched"]) == want
    assert list(got) == ["answered_off_device", "rows_not_dispatched"]


@pytest.mark.parametrize("name", ["flagship32", "flagship32-data4"])
def test_an_accepted_configuration_gives_the_parents_two_expressions(name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert config["guarantees"]["answers_from"] == {
        "device": "policy_server_dispatched_rows"}
    moved = {"device": 29_000, "host_fastpath": 600, "oracle_fallback": 5,
             "breaker_trip": 1, "breaker_short_circuit": 300,
             "deadline_abandoned": 2}
    before, after = _samples(moved)
    got = reduce.held_to_its_sources(config, before, after, 29_905)
    assert got["answered_off_device"] == sum(
        reduce.delta(before, after, c) for c in PARENTS_MUST_STAY_ZERO) == 908
    assert got["rows_not_dispatched"] == abs(29_905 - reduce.delta(
        before, after, "policy_server_dispatched_rows")) == 905


def test_a_program_without_a_sources_counter_has_no_such_source():
    counter = "policy_server_dispatched_rows_total"
    before = reduce.parse_metrics(f"{counter} 10\n")
    after = reduce.parse_metrics(f"{counter} 25\n")
    assert reduce.answers_by_source(before, after) == {
        **dict.fromkeys(SOURCES, 0), "device": 15}
    assert reduce.held_to_its_sources(DEVICE_ALONE, before, after, 15) == {
        "answered_off_device": 0, "rows_not_dispatched": 0}


def test_every_source_names_a_counter_and_what_it_counts():
    assert set(PARENTS_MUST_STAY_ZERO) < {
        spec["counter"] for spec in SOURCES.values()}
    for spec in SOURCES.values():
        assert spec["counter"].startswith("policy_server_")
        assert spec["counts"] in ("requests", "events") and spec["what"]
    assert "policy_server_fragment_hits" not in {
        spec["counter"] for spec in SOURCES.values()}  # a subset of the hits
