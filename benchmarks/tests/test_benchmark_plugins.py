"""The seams through which a later configuration or mix brings its own
yardstick as new files, and what the harness does with them:

* a configuration's ``"reference": "<module>"`` (``references/<module>.py``)
  judges its cells, and a mix's ``"generator": "<name>"``
  (``generators/<name>.py``) makes their requests, for the clients, the
  control and the judge alike;
* ``check_manifest`` refuses such a module that is missing, imports
  anything of the program or lacks a name the harness calls;
* today's mixes and configurations read byte for byte as before the seams:
  digests of their requests and of the reference's answers, written in;
* a mix with a warm state (``warm/<mix>.json``) warms as before, then
  until the lane's first pause after the passes is as old as it says;
  every other mix warms exactly as before.

The plug-ins here are written into ``tmp_path``. CPU only; the two cases
that boot the server under test take ~30 s each.

    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import argparse
import copy
import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check_manifest  # noqa: E402
import reduce  # noqa: E402
import run  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = ("rollout-saturate", "tiny-closed", "unique-saturate", "unique-steady",
         "unique-trickle")
CONFIGS = tuple(c["name"] for c in MANIFEST["configs"])
SEEDS = (7, 2**31 + 11)
N = 4096

# -- plug-ins, as a later PR would add them --------------------------------------

# a reference that denies what the canary policy admits: a yardstick that
# differs from the benchmark's own in a way a run can see
DENY_CANARY = '''"""The plain reference, but the canary policy denies."""
import reference
from reference import MODULES, http_response  # noqa: F401


def review_response(entry, request, signed):
    out = reference.review_response(entry, request, signed)
    if entry.get("module") == "builtin://always-happy":
        out = dict(out, allowed=False,
                   status={"message": "denied by the plug-in", "code": 400})
    return out
'''

# pod reviews whose uid names the generator that made them: only the
# clients and the judge using this module agree on the answers' uids
PLUGGED = '''"""Pod reviews with a uid of this generator's own."""
import traffic

MARK, OURS = b'"uid":"synthetic-', b'"uid":"plugged-x-'


def uid_of(n):
    return "plugged-x-" + traffic.uid_of(n)[len("synthetic-"):]


class Traffic(traffic.Traffic):
    def __init__(self, mix, seed, policy_ids):
        super().__init__(dict(mix, generator="pod_reviews"), seed, policy_ids)

    def request(self, n):
        return super().request(n).replace(MARK, OURS, 1)
'''


def _plugged_in(tmp_path: Path, monkeypatch, files: dict[str, str]) -> None:
    """Write plug-in modules under ``tmp_path`` and resolve every named
    one there (the benchmark's own stay where they are)."""
    for name, text in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(text)
    resolve = check_manifest.plugin_file
    monkeypatch.setattr(check_manifest, "plugin_file",
                        lambda kind, name, bench=None:
                        resolve(kind, name, tmp_path))


def _run(monkeypatch, capsys, *, reference: str | None = None,
         generator: str | None = None, control: str | None = None) -> dict:
    """One run of ``flagship32`` under a tiny closed mix, through
    ``run.main --platform cpu``: the server under test, or with
    ``control`` the reference in its place."""
    manifest = copy.deepcopy(MANIFEST)
    cell = "flagship32.plugged"
    manifest["workloads"].append({
        "name": cell, "config": "flagship32", "traffic": "plugged",
        "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        m.get("workloads", []).append(cell)
    monkeypatch.setattr(check_manifest, "load", lambda root=None: manifest)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    load_json = run.load_json

    def data(path: Path) -> dict:
        """The tiny mix over 31 shapes (all of them met while warming, so
        nothing compiles in the window), under the mix's and the
        configuration's plug-ins."""
        if path.name == "plugged.json":
            doc = dict(load_json(BENCH / "traffic" / "tiny-closed.json"),
                       pool_shapes=31, warm_requests=256)
            return dict(doc, generator=generator) if generator else doc
        doc = load_json(path)
        if path.name == "flagship32.json" and reference:
            doc = dict(doc, reference=reference)
        return doc

    monkeypatch.setattr(run, "load_json", data)
    argv = ["--workload", cell, "--seed", str(2**31 + 21), "--seconds", "1.5",
            "--trace", "0", "--platform", "cpu"]
    rc = run.main(argv + (["--control", control] if control else []))
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["attempted"] > 50 and result["failed"] == 0
    return result


# -- a configuration's own reference, a mix's own generator ------------------------


@pytest.mark.parametrize("control", ["none", None], ids=["control", "program"])
def test_a_configuration_is_judged_by_its_own_reference(
        control, tmp_path, monkeypatch, capsys):
    _plugged_in(tmp_path, monkeypatch,
                {"references/deny_canary.py": DENY_CANARY})
    result = _run(monkeypatch, capsys, reference="deny_canary",
                  control=control)
    mismatched = result["compared"]["mismatched"][0]
    if control:  # the control answers by the plug-in too: they agree
        assert result["correct"] is True and mismatched == 0
    else:  # the program admits what the plug-in denies: one policy of 32
        assert result["correct"] is False
        assert 0 < mismatched < result["attempted"] / 8


@pytest.mark.parametrize("control", ["none", None], ids=["control", "program"])
def test_a_mix_is_driven_and_judged_through_its_own_generator(
        control, tmp_path, monkeypatch, capsys):
    _plugged_in(tmp_path, monkeypatch, {"generators/plugged.py": PLUGGED})
    result = _run(monkeypatch, capsys, generator="plugged", control=control)
    # the answers carry the plug-in's uids, and the judge expects them
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["mismatched"] == [0, 0]


def test_the_generator_and_reference_are_loaded_from_their_files(
        tmp_path, monkeypatch):
    _plugged_in(tmp_path, monkeypatch, {
        "generators/plugged.py": PLUGGED,
        "references/deny_canary.py": DENY_CANARY})
    gen = check_manifest.plugin("generator", "plugged")
    assert Path(gen.__file__) == tmp_path / "generators" / "plugged.py"
    ids = [f"p{i}" for i in range(32)]
    traffic = gen.Traffic({"generator": "plugged", "pool_shapes": 31}, 5, ids)
    body = traffic.request(40).partition(b"\r\n\r\n")[2]
    assert json.loads(body)["request"]["uid"] == gen.uid_of(40) == \
        "plugged-x-0000000040"
    ref = check_manifest.plugin("reference", "deny_canary")
    assert ref.review_response({"module": "builtin://always-happy"},
                               {"uid": "u"}, set())["allowed"] is False
    # where nothing is named, the benchmark's own modules
    import reference
    import traffic as own

    assert check_manifest.plugin("reference", None) is reference
    assert check_manifest.plugin("generator", "pod_reviews") is own


# -- what check_manifest refuses ---------------------------------------------------


def _copy(tmp_path: Path) -> Path:
    """The benchmark's data files in a root of their own (the code stays
    where it is)."""
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.py"))
    return tmp_path / "benchmarks"


def _name_reference(bench: Path, module: str) -> None:
    file = bench / "configs" / "flagship32.json"
    file.write_text(json.dumps(dict(json.loads(file.read_text()),
                                    reference=module)))


def _name_generator(bench: Path, module: str) -> None:
    file = bench / "traffic" / "unique-trickle.json"
    file.write_text(json.dumps(dict(json.loads(file.read_text()),
                                    generator=module)))


SOUND_REFERENCE = DENY_CANARY
SOUND_GENERATOR = PLUGGED


@pytest.mark.parametrize("kind, text, reason", [
    ("reference", SOUND_REFERENCE, None),
    ("generator", SOUND_GENERATOR, None),
    ("reference", None, "no module references/mod.py"),
    ("generator", None, "no module generators/mod.py"),
    ("reference", "import policy_server_tpu\n" + SOUND_REFERENCE,
     "imports policy_server_tpu"),
    ("reference", "from policy_server_tpu.policies import library\n"
     + SOUND_REFERENCE, "imports policy_server_tpu.policies"),
    ("generator", SOUND_GENERATOR + "\ndef _later():\n    import importlib\n"
     "    return importlib.import_module('policy_server_tpu.api')\n",
     "imports policy_server_tpu.api"),
    ("reference", SOUND_REFERENCE.replace("MODULES, http_response",
                                          "MODULES"),
     "defines no http_response"),
    ("reference", SOUND_REFERENCE.replace("def review_response",
                                          "def answer"),
     "defines no review_response"),
    ("generator", SOUND_GENERATOR.replace("def uid_of", "def uid"),
     "defines no uid_of"),
    ("generator", "class Traffic:\n    def request(self, n):\n        "
     "return b''\n\n\ndef uid_of(n):\n    return str(n)\n",
     "its Traffic has no shape_of"),
], ids=["sound reference", "sound generator", "no reference module",
        "no generator module", "import", "from-import", "import_module",
        "no http_response", "no review_response", "no uid_of",
        "no shape_of"])
def test_the_manifest_check_refuses_a_plugin(kind, text, reason, tmp_path):
    bench = _copy(tmp_path)
    assert check_manifest.problems(MANIFEST, tmp_path) == []
    folder = check_manifest.PLUGINS[kind][0]
    if text is not None:
        (bench / folder).mkdir()
        (bench / folder / "mod.py").write_text(text)
    (_name_reference if kind == "reference" else _name_generator)(bench, "mod")
    found = check_manifest.problems(MANIFEST, tmp_path)
    if reason is None:
        assert found == []
    else:
        assert any(reason in p for p in found), found
        with pytest.raises(check_manifest.ManifestError, match=reason):
            check_manifest.plugin(kind, "mod", bench)


def test_a_plugin_name_is_a_name_not_a_path(tmp_path):
    bench = _copy(tmp_path)
    _name_reference(bench, "../reference")
    found = check_manifest.problems(MANIFEST, tmp_path)
    assert any("must match" in p for p in found), found


# -- today's cells read byte for byte ----------------------------------------------

# sha256 over the first 4,096 requests of each mix and seed, and over the
# reference's answers to 4,096 requests of unique-saturate (seed 7) under
# each configuration; taken from the harness before configurations and
# mixes could name modules of their own (traffic.Traffic and reference.py
# called directly)
REQUESTS = {
    ("rollout-saturate", 7):
        "4bb93181f992b38d82ec14c76e3792d0bc6aae5b2bc9c71521d4162294609fc0",
    ("rollout-saturate", 2**31 + 11):
        "cc17610b1ea8ae014f91da4bb3c4024663fb55c523b90aaf0a70f359072a45fe",
    ("tiny-closed", 7):
        "3ef415b54970878001ffdc2738314b8e1f47f429a54e098a1752e386d38e4c9c",
    ("tiny-closed", 2**31 + 11):
        "906eef2daa8d32a5b6d5437ec068b442ac8b80eb90b25086c6c8c841476a9956",
    ("unique-saturate", 7):
        "058d53f0e15556f32e657325c3e622b2f8c462082476f99e39b49a2608f64aba",
    ("unique-saturate", 2**31 + 11):
        "b622a842400fff945ef8e49f7a49d92407192e3338924a4fc21b57c69628ed06",
    ("unique-steady", 7):
        "058d53f0e15556f32e657325c3e622b2f8c462082476f99e39b49a2608f64aba",
    ("unique-steady", 2**31 + 11):
        "b622a842400fff945ef8e49f7a49d92407192e3338924a4fc21b57c69628ed06",
    ("unique-trickle", 7):
        "058d53f0e15556f32e657325c3e622b2f8c462082476f99e39b49a2608f64aba",
    ("unique-trickle", 2**31 + 11):
        "b622a842400fff945ef8e49f7a49d92407192e3338924a4fc21b57c69628ed06",
}
ANSWERS = dict.fromkeys(
    CONFIGS, "6fa55bc635c7f79c4268a1c3c9ae6eba8bde03a52e6f9a1ee66563c2f22d1135")


def _load(kind: str, name: str) -> dict:
    folder = "traffic" if kind == "mix" else "configs"
    return json.loads((BENCH / folder / f"{name}.json").read_text())


POLICY_IDS = list(_load("config", "flagship32")["policies"])


@pytest.mark.parametrize("mix, seed", list(REQUESTS))
def test_a_mixs_requests_are_as_before(mix, seed):
    doc = _load("mix", mix)
    generator = check_manifest.plugin("generator", doc["generator"])
    traffic = generator.Traffic(doc, seed, POLICY_IDS)
    digest = hashlib.sha256()
    for n in range(N):
        digest.update(traffic.request(n))
    assert digest.hexdigest() == REQUESTS[(mix, seed)]


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configurations_reference_answers_are_as_before(name):
    config = _load("config", name)
    reference = check_manifest.plugin("reference", config.get("reference"))
    policies = run.fill(config["policies"], {"@SIGSTORE@": "/sigstore",
                                             "@PUBKEY@": "PUBKEY"})
    ids = list(policies)
    mix = _load("mix", "unique-saturate")
    generator = check_manifest.plugin("generator", mix["generator"])
    traffic = generator.Traffic(mix, 7, ids)
    signed = set(config["signing"]["signed_images"])
    digest = hashlib.sha256()
    for n in range(N):
        digest.update(reference.http_response(
            config["response_head"], generator.uid_of(n),
            reference.review_response(
                policies[ids[traffic.policy_of(n)]],
                traffic.reviews[traffic.shape_of(n)]["request"], signed)))
    assert digest.hexdigest() == ANSWERS[name]


# -- the warm state ------------------------------------------------------------------

COMPILES = ("policy_server_xla_programs_compiled_total",
            "policy_server_plane_program_compiles_total")
LANE = "audit_dispatch"


class _Warming:
    """The clients and the server of a run as ``Rig._warm`` sees them:
    each pass compiles what ``compiles`` says, then nothing; every pass or
    chunk stamps the lane's phase in the ring as it begins and ends, but the
    one that holds request number ``pause_at``, which first keeps the lane
    quiet for ``pause_s``; ``stamp_s`` long stamps, if given, stand for a
    lane job that takes that long."""

    def __init__(self, compiles: list[int], pause_at: int | None,
                 pause_s: float, stamp_s: float = 0.0) -> None:
        self.compiles, self.compiled, self.passes = compiles, 0, []
        self.pause_at, self.pause_s, self.stamp_s = pause_at, pause_s, stamp_s
        self.sent, self.events = 0, []
        self.ready_port = 0

    def _stamp(self, name: str = LANE) -> None:
        end = time.monotonic_ns()
        self.events.append((name, end - int(self.stamp_s * 1e9) - 10**6, end))

    def warm(self, base: int, count: int) -> tuple[int, int]:
        self.passes.append((base, count))
        at = len(self.passes) - 1
        self.compiled += self.compiles[at] if at < len(self.compiles) else 0
        if self.pause_at is not None and base <= self.pause_at < base + count:
            self._stamp()
            time.sleep(self.pause_s)
            self.paused_at = time.monotonic()
        self._stamp()
        time.sleep(0.01)
        self._stamp("host_eval")
        self._stamp()
        self.sent += count
        return count, 0

    def metrics(self) -> reduce.Samples:
        return reduce.parse_metrics(
            "policy_server_plane_programs_pending 0\n"
            f"{COMPILES[0]} {self.compiled}\n{COMPILES[1]} 0\n")

    def timeline(self, port: int, since: int, until: int) -> dict:
        return {"traceEvents": [
            {"ph": "X", "pid": 1, "name": name, "ts": start / 1e3,
             "dur": (end - start) / 1e3}
            for name, start, end in list(self.events)
            if end >= since and start <= until]}


PAUSING = {"why": "a test's", "pause_phase": LANE, "pause_quiet_s": 0.05,
           "open_after_pause_s": 0.1, "chunk_requests": 512}


def _warm(mix: str, compiles: list[int], state: dict | None = None,
          pause_at: int | None = None, pause_s: float = 0.08,
          stamp_s: float = 0.0, control: str | None = None) -> _Warming:
    """``Rig._warm`` over the fake, under the mix's own warm state or
    ``state`` in its place, the ring read every 5 ms and a pause waited
    for 1 s at most."""
    rig = object.__new__(run.Rig)
    rig.args = argparse.Namespace(warm_passes=8, ready_timeout=1.0,
                                  control=control)
    rig.mix, rig.cell, rig.sent = _load("mix", mix), {"traffic": mix}, 0
    rig.clients = rig.server = _Warming(compiles, pause_at, pause_s, stamp_s)
    rig.warm_state = _state(mix) if state is None else state
    saved = run.PAUSE_POLL_S, run.PAUSE_WAIT_S, run.host_spans.fetch_timeline
    run.PAUSE_POLL_S, run.PAUSE_WAIT_S = 0.005, 1.0
    run.host_spans.fetch_timeline = rig.server.timeline
    try:
        rig._warm(rig.server.metrics())
    finally:
        (run.PAUSE_POLL_S, run.PAUSE_WAIT_S,
         run.host_spans.fetch_timeline) = saved
    assert rig.sent == rig.clients.sent
    return rig.clients


def _state(mix: str) -> dict:
    file = BENCH / "warm" / f"{mix}.json"
    return json.loads(file.read_text()) if file.is_file() else {}


@pytest.mark.parametrize("compiles", [[5], [5, 2], [5, 2, 1]],
                         ids=["once", "twice", "thrice"])
@pytest.mark.parametrize("mix", MIXES)
def test_a_mix_without_a_warm_state_warms_as_before(mix, compiles):
    """Passes of ``warm_requests`` until a pass after the first compiled
    nothing, and not a request more."""
    count = _load("mix", mix)["warm_requests"]
    passes = _warm(mix, compiles, state={}).passes
    assert passes == [(i * count, count)
                      for i in range(max(2, len(compiles) + 1))]


@pytest.mark.parametrize("chunk", [0, 1, 4])
def test_chunks_go_on_until_the_first_pause_is_old_enough(chunk):
    count = _load("mix", "unique-trickle")["warm_requests"]
    warming = _warm("unique-trickle", [5], PAUSING,
                    pause_at=2 * count + chunk * 512)
    chunks = warming.passes[2:]
    assert warming.passes[:2] == [(0, count), (count, count)]
    assert chunks and all(c == 512 for _b, c in chunks)
    assert chunks[0][0] == 2 * count and len(chunks) > chunk
    # the window may open: the pause began open_after_pause_s ago or more
    assert time.monotonic() - warming.paused_at + 0.08 >= 0.1


def test_a_pause_that_began_late_in_the_passes_counts():
    """One that began less than ``open_after_pause_s`` before the passes
    ended: the chunks go on until it is that old, and no longer."""
    count = _load("mix", "unique-trickle")["warm_requests"]
    warming = _warm("unique-trickle", [5], dict(PAUSING,
                                                open_after_pause_s=0.5),
                    pause_at=count + 1, pause_s=0.2)
    assert warming.passes[:2] == [(0, count), (count, count)]
    assert all(c == 512 for _b, c in warming.passes[2:])
    age = time.monotonic() - warming.paused_at + 0.2
    assert 0.5 <= age < 0.5 + 0.2


def test_a_pause_that_began_earlier_is_no_mark():
    """One that began longer than ``open_after_pause_s`` before the passes
    ended would open the window later in the lane's round than its age
    says: the chunks wait for the next pause (here none comes)."""
    with pytest.raises(run.RunFailure, match="no pause of audit_dispatch"):
        _warm("unique-trickle", [5], dict(PAUSING, open_after_pause_s=0.05),
              pause_at=1, pause_s=0.2)


def test_a_long_lane_job_is_no_pause():
    with pytest.raises(run.RunFailure, match="no pause of audit_dispatch"):
        _warm("unique-trickle", [5], PAUSING, stamp_s=0.2)


def test_a_lane_that_never_pauses_fails_the_run():
    with pytest.raises(run.RunFailure, match="no pause of audit_dispatch"):
        _warm("unique-trickle", [5], PAUSING)


def test_the_control_sends_one_pass_whatever_the_state():
    warming = _warm("unique-trickle", [5], PAUSING, control="none")
    assert warming.passes == [(0, _load("mix", "unique-trickle")[
        "warm_requests"])]


def test_only_the_trickle_mix_has_a_warm_state():
    assert sorted(p.stem for p in (BENCH / "warm").glob("*")) == [
        "unique-trickle"]
    # and no mix has a key of it: the mixes read as they did
    warm_keys = set(_state("unique-trickle")) - {"why"}
    for mix in MIXES:
        assert not warm_keys & set(_load("mix", mix))


@pytest.mark.parametrize("state, reason", [
    (PAUSING, None),
    (dict(PAUSING, warm_total_requests=32768), "a warm state holds"),
    ({"pause_phase": LANE}, "a warm state holds"),
    (dict(PAUSING, chunk_requests=0), "whole number"),
    (dict(PAUSING, chunk_requests="512"), "whole number"),
    (dict(PAUSING, pause_phase="a phase"), "a phase's name"),
    (dict(PAUSING, pause_quiet_s=0), "seconds over 0"),
    (dict(PAUSING, open_after_pause_s="2"), "seconds over 0"),
], ids=["pause", "unknown key", "phase alone", "empty chunk",
        "chunk not a number", "phase not a name", "no quiet",
        "after not a number"])
def test_the_manifest_check_refuses_a_warm_state(state, reason, tmp_path):
    bench = _copy(tmp_path)
    (bench / "warm").mkdir(exist_ok=True)
    (bench / "warm" / "unique-trickle.json").write_text(json.dumps(state))
    found = check_manifest.problems(MANIFEST, tmp_path)
    if reason is None:
        assert found == []
    else:
        assert any(reason in p for p in found), found
