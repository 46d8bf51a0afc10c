"""A configuration with a verifier, added as files only, through the
harness: the fixture under ``fixtures/`` (a ``BENCHMARK.json``, a
configuration with a ``verifier`` block, the stand-in plug-in
``models/row_margin.py`` and a closed-loop mix) is reached through the
seams where the harness reads ``BENCHMARK.json``, the plug-ins and the
mixes. A sound rehearsal comes out correct; each way the verifier's part of
the timed path can be broken comes out not correct, by the check named.
The plans of the existing cells are pinned, to show them unchanged.
"""
import argparse
import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import run, traffic
from bench.flops import prefill_flops
from bench.reference import row_verdicts
from bench.world import build_world
from repro.configs import ModelConfig, MoEConfig

FIXTURES = Path(__file__).resolve().parent / "fixtures"
CELL = "fixture.closed"
SEED = 2 ** 31 + 1601


def use_fixture(mp):
    mp.setattr(run, "spec_file", lambda: FIXTURES / "BENCHMARK.json")
    mp.setattr(run, "MODELS", FIXTURES / "models")
    mp.setattr(traffic, "TRAFFIC_DIR", FIXTURES / "traffic")


def rehearse(cell=CELL, seconds=2.0, warmup=True):
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds,
                              trace=0, rehearse=True, sweep="")
    out, rc = run.execute(args, warmup=warmup)
    assert rc == 1
    return out


def spy(mp, name):
    """Wrap ``run.<name>`` and keep what each call returned, with its
    arguments."""
    real, seen = getattr(run, name), []

    def wrapped(*a, **kw):
        out = real(*a, **kw)
        seen.append((a, kw, out))
        return out
    mp.setattr(run, name, wrapped)
    return seen


@pytest.fixture(scope="module")
def sound():
    """One sound rehearsal of the fixture cell: its result, what the
    closed loop served, and what the verifier did."""
    with pytest.MonkeyPatch.context() as mp:
        use_fixture(mp)
        loops, checks = spy(mp, "closed_loop"), spy(mp, "check")
        out = rehearse()
    [(_, _, (served, _, _))] = loops
    [(args, _, _)] = checks
    return out, served, args[6]


def plugin():
    return run._load(FIXTURES / "models" / "row_margin.py", "bench_model")


def test_sound_rehearsal_is_correct(sound):
    out, served, verified = sound
    assert out["correct"] and out["failed"] == 0, out
    assert list(out["checks"]) == [
        "wrong_answers", "unanswered", "search_rank_errors",
        "search_score_error", "unverified_rows", "verifier_margin_error",
        "verifier_sign_errors"]
    checks = out["checks"]
    assert checks["unverified_rows"]["value"] == 0
    assert checks["verifier_sign_errors"]["value"] == 0
    assert checks["verifier_margin_error"]["limit"] == plugin().TOLERANCE
    assert checks["verifier_margin_error"]["value"] < 1e-2 * \
        plugin().TOLERANCE
    # the verifier's verdicts decide answers: it said both yes and no
    _, said, margins = verified.rows()
    assert 0 < said.sum() < len(said)
    assert (said == (margins > 0)).all()
    assert verified.model is None
    assert out["attempted"] >= 20
    assert sum(bool(s.ticket.result.segments) for s in served) \
        >= len(served) // 4


def test_closed_loop_keeps_one_query_per_client(sound):
    _, served, _ = sound
    by_client = {}
    for s in served:
        assert s.due == s.submitted
        by_client.setdefault(s.q["session"], []).append(s)
    assert sorted(by_client) == [0, 1]
    for mine in by_client.values():
        mine.sort(key=lambda s: s.submitted)
        for a, b in zip(mine, mine[1:]):
            assert b.submitted >= a.ticket.completed_at
    assert {s.q["verify_budget"] for s in served} == {0}


def test_a_margin_off_by_twice_the_tolerance(monkeypatch):
    """One sampled row's margins, as the program computed them, are off by
    2 x TOLERANCE x the reference's RMS."""
    use_fixture(monkeypatch)
    pl = plugin()
    real = run.VerifierRecorder.host

    def host(self):
        v = real(self)
        asked = v.rows()[0]
        sample = run.margin_sample(asked, SEED)
        cfg = dict(run.load_cell(CELL)["cfg"], **run.REHEARSAL)
        want = pl.reference_margins(cfg, sample, world=None, seed=SEED,
                                    rehearse=True)
        shift = 2 * pl.TOLERANCE * np.sqrt(np.mean(want ** 2))
        for c in v.calls:
            hit = (c.rows == sample[0]).all(axis=1)
            c.margins = c.margins + shift * hit
        return v
    monkeypatch.setattr(run.VerifierRecorder, "host", host)
    out = rehearse()
    assert not out["correct"]
    err = out["checks"]["verifier_margin_error"]
    assert err["value"] == pytest.approx(2 * err["limit"], rel=0.01)
    assert out["checks"]["wrong_answers"]["value"] == 0


def test_verdicts_that_do_not_follow_the_margins(monkeypatch):
    """The verifier's verdicts are the opposite of its margins' signs: the
    answers follow the verdicts, so only the sign check can tell."""
    use_fixture(monkeypatch)
    real = run.VerifierRecorder.__init__

    def init(self, verifier):
        inner = verifier.verify
        verifier.verify = lambda rows: ~inner(rows)
        real(self, verifier)
    monkeypatch.setattr(run.VerifierRecorder, "__init__", init)
    out = rehearse()
    assert not out["correct"]
    assert out["checks"]["verifier_sign_errors"]["value"] > 0
    assert out["checks"]["wrong_answers"]["value"] == 0


def test_the_engine_told_yes_where_the_verifier_said_no(monkeypatch):
    """In every call, one row the verifier rejected reaches the engine as
    accepted."""
    use_fixture(monkeypatch)
    real = run.VerifierRecorder.verify

    def verify(self, rows):
        out = np.array(real(self, rows))
        no = np.nonzero(~out)[0]
        if len(no):
            out[no[0]] = True
        return out
    monkeypatch.setattr(run.VerifierRecorder, "verify", verify)
    out = rehearse()
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_a_candidate_row_never_sent_to_the_verifier(monkeypatch):
    """The first row the verifier would have been asked about in the
    window is never sent to it, in any call; the engine takes it as
    rejected."""
    use_fixture(monkeypatch)
    real, real_loop = run.VerifierRecorder.verify, run.closed_loop
    dropped, window = [], []

    def loop(*a, **kw):
        window.append(True)
        return real_loop(*a, **kw)

    def verify(self, rows):
        rows = np.asarray(rows)
        if not window:
            return real(self, rows)
        if not dropped:
            dropped.append(rows[0].copy())
        keep = ~(rows == dropped[0]).all(axis=1)
        out = np.zeros(len(rows), bool)
        out[keep] = real(self, rows[keep])
        return out
    monkeypatch.setattr(run.VerifierRecorder, "verify", verify)
    monkeypatch.setattr(run, "closed_loop", loop)
    out = rehearse()
    assert not out["correct"]
    assert out["checks"]["unverified_rows"]["value"] == 1
    assert out["checks"]["wrong_answers"]["value"] == 0


def test_a_verifier_bypassed_entirely(monkeypatch):
    import repro.session
    use_fixture(monkeypatch)
    real = repro.session.open_video_store

    def bypass(stores, embedder, **kw):
        return real(stores, embedder, **dict(kw, verifier=None))
    monkeypatch.setattr(repro.session, "open_video_store", bypass)
    out = rehearse()
    assert not out["correct"]
    assert out["checks"]["unverified_rows"]["value"] > 0


class VerdictsOnly:
    """A verifier that gives verdicts and no margins."""

    def __init__(self, inner):
        self.inner, self.calls = inner, 0

    def verify(self, rows):
        return self.inner.verify(rows)


class MarginsApart(VerdictsOnly):
    """A verifier with margins whose verify does not go through them."""

    def margins(self, rows):
        return self.inner.margins(rows)


@pytest.mark.parametrize("wrap,error,match", [
    (VerdictsOnly, SystemExit, "has no margins"),
    (MarginsApart, RuntimeError, "1 verdicts from 0 margins")])
def test_a_verifier_that_hides_its_margins_is_refused(wrap, error, match,
                                                      monkeypatch):
    """Without the margins its verify thresholds, the margin check would
    see nothing: such a verifier is refused before any query."""
    use_fixture(monkeypatch)
    real = run.load_plugin

    def load(name):
        pl = real(name)
        build = pl.build
        pl.build = lambda *a, **kw: wrap(build(*a, **kw))
        return pl
    monkeypatch.setattr(run, "load_plugin", load)
    loops = spy(monkeypatch, "closed_loop")
    with pytest.raises(error, match=match):
        rehearse()
    assert not loops


def test_a_configuration_without_verifier_keeps_its_four_checks():
    args = argparse.Namespace(workload="archive.chains", seed=SEED,
                              seconds=2.0, trace=0, rehearse=True, sweep="")
    out, rc = run.execute(args, warmup=False)
    assert rc == 1 and out["correct"], out
    assert list(out["checks"]) == ["wrong_answers", "unanswered",
                                   "search_rank_errors",
                                   "search_score_error"]


def test_a_verifier_key_the_plugin_does_not_take_is_refused(tmp_path,
                                                            monkeypatch):
    use_fixture(monkeypatch)
    cfg = json.loads((FIXTURES / "configs"
                      / "row-margin-archive.json").read_text())
    cfg["verifier"]["precision"] = "int8"
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "row-margin-archive.json").write_text(
        json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(
        (FIXTURES / "BENCHMARK.json").read_text())
    monkeypatch.setattr(run, "spec_file", lambda: tmp_path / "BENCHMARK.json")
    with pytest.raises(SystemExit, match="verifier.precision"):
        run.load_cell(CELL)


@pytest.mark.parametrize("name", ["both", "neither", "sessions"])
def test_a_mix_names_one_loop(name, tmp_path, monkeypatch):
    """A mix gives exactly one of rate_per_s and clients, and a closed
    loop, whose client c is session c, takes no sessions."""
    mix = json.loads((FIXTURES / "traffic" / "closed.json").read_text())
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    m, match = {"both": (dict(mix, rate_per_s=1.0), "one of rate_per_s"),
                "neither": ({k: v for k, v in mix.items() if k != "clients"},
                            "one of rate_per_s"),
                "sessions": (dict(mix, sessions=2), "sessions")}[name]
    (tmp_path / f"{name}.json").write_text(json.dumps(m))
    with pytest.raises(ValueError, match=match):
        traffic.load_mix(name)


def test_every_seed_streams_the_same_work():
    cfg = dict(run.load_cell("archive.chains")["cfg"], videos=600)
    world = build_world(cfg, SEED)
    mix = dict(traffic.load_mix("interactive"), verify_budget=4)
    del mix["rate_per_s"], mix["sessions"]
    mix["clients"] = 4

    def take(seed, n=60):
        it = traffic.stream(mix, seed, world, list(cfg["predicates"]))
        return [next(it) for _ in range(n)]
    a, b = take(SEED), take(SEED + 1)
    assert [(q["cls"], q["top_k"]) for q in a] == [(q["cls"], q["top_k"])
                                                    for q in b]
    assert [q["entities"] for q in a] != [q["entities"] for q in b]
    assert a == take(SEED)
    # each block of 20 holds the shares: 3/4 grounded, half at each top_k
    assert sum(q["cls"] == "triple" for q in a[:20]) == 15
    assert sum(q["top_k"] == 16 for q in a[:20]) == 10
    assert {q["verify_budget"] for q in a} == {4}
    assert run.to_query(a[0]).verify_budget == 4


# sha256 of the JSON of traffic.schedule and of traffic.warmup_batches at
# SEED for each existing mix on ag-archive-d4096, at its own size and
# run_seconds, computed on the tree before closed loops and verify_budget
# were added
PINNED = {
    "interactive": (
        82,
        "d76568b70fd1afd489abe366f4b56ca707c2ffaeb47eacbe70047d957fde2e40",
        16,
        "db43ec48481103e1f1d1f9fca0ec4bb86e21965211b1b5c8d3adf988d8bb674f"),
    "chains": (
        15,
        "8c112c9ebafa829f508cd5c7e69f7bb4805468ec835ea0bb8e5440f1bbdb2fb7",
        8,
        "d93922dea1a7e70764cff187dae2cca8d952eede6373e64974a8c31a03d75147"),
}


@pytest.mark.parametrize("mix_name", sorted(PINNED))
def test_existing_plans_are_unchanged(mix_name):
    spec = run.load_cell("archive.chains")
    cfg = spec["cfg"]
    world = build_world(cfg, SEED)
    mix = traffic.load_mix(mix_name)
    preds = list(cfg["predicates"])
    plan = traffic.schedule(mix, spec["run_seconds"], SEED, world, preds)
    warm = list(traffic.warmup_batches(mix, run.MAX_BATCH, world, preds,
                                       SEED))

    def digest(x):
        return hashlib.sha256(json.dumps(x, sort_keys=True).encode()
                              ).hexdigest()
    assert (len(plan), digest(plan), len(warm), digest(warm)) == PINNED[mix_name]


def test_row_verdicts_lower_every_yes_upper_any_yes():
    rows = np.array([[0, 0, 1, 2, 3], [0, 1, 1, 2, 3], [1, 0, 0, 1, 2],
                     [1, 5, 2, 0, 1]])
    asked = np.array([[0, 0, 1, 2, 3], [0, 1, 1, 2, 3], [0, 1, 1, 2, 3],
                      [1, 0, 0, 1, 2]])
    said = np.array([True, True, False, False])
    lo, hi, seen = row_verdicts(rows, asked, said)
    # yes; yes then no; no; never asked
    assert lo.tolist() == [True, False, False, False]
    assert hi.tolist() == [True, True, False, True]
    assert seen.tolist() == [True, True, True, False]


def test_prefill_flops_counts_gelu_and_experts_per_token():
    dense = ModelConfig(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
                        head_dim=4, d_ff=16, vocab_size=32,
                        mlp_activation="gelu")
    f = prefill_flops(dense, batch=3, seq=5)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 2 * 8 * 16
    assert f["matmul"] == 2 * 15 * 2 * per_layer
    assert f["attention"] == 4 * 3 * 2 * 25 * 4 * 2
    assert f["lm_head"] == 2 * 3 * 8 * 32
    moe = dataclasses.replace(dense, mlp_activation="silu", d_ff=0,
                              moe=MoEConfig(num_experts=6,
                                            experts_per_token=2,
                                            d_ff_expert=16))
    g = prefill_flops(moe, batch=3, seq=5)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 8 * 6 + 2 * 3 * 8 * 16
    assert g["matmul"] == 2 * 15 * 2 * per_layer
