"""The check that decides ``correct``: a run with the timed path broken
underneath reads not correct, once for each fault a served cell can
have; the float8 control reads above the limit where the program reads
below it."""

import numpy as np
import pytest

import run as R
from test_run_smoke import run_once

SEED = 2**31 + 23


def test_sound_run_is_correct(bench_copy):
    res, _, _, err = run_once(bench_copy, "smoke.chat", SEED, 3.0)
    assert res["correct"], err


def altered_tokens(monkeypatch):
    """A token altered where it is produced: every decoded token id
    moved one up, as a wrong argmax or a corrupt readback would."""
    from repro.serve import engine as E
    orig = E.ServeEngine._dispatch

    def dispatch(self, sub):
        return (orig(self, sub) + 1) % self.cfg.vocab_size
    monkeypatch.setattr(E.ServeEngine, "_dispatch", dispatch)


def state_unchanged(monkeypatch):
    """A decode step that returns its cache unchanged: no new K/V row
    is ever written, so later tokens attend to what was there."""
    from repro.serve import engine as E
    orig = E.make_chunked_decode_step

    def make(*a, **k):
        step = orig(*a, **k)

        def broken(params, cache, *rest):
            out = step(params, cache, *rest)
            return (out[0], cache) + tuple(out[2:])
        return broken
    monkeypatch.setattr(E, "make_chunked_decode_step", make)


def wrong_prefix_pages(monkeypatch):
    """A prefix index whose key forgets the tokens: a prompt maps the
    pages of an earlier prompt that merely had as many full pages (the
    index's answer altered where it is produced)."""
    from repro.serve import pages as P
    monkeypatch.setattr(P.PagePool, "_chain",
                        staticmethod(lambda prev, tokens: (prev, len(tokens))))


@pytest.mark.parametrize("fault", [altered_tokens, state_unchanged,
                                   wrong_prefix_pages])
def test_fault_reads_not_correct(bench_copy, monkeypatch, fault):
    fault(monkeypatch)
    res, _, _, err = run_once(bench_copy, "smoke.chat", SEED, 3.0)
    assert res["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in res["check"].values())


def test_control_reads_above_the_limit(bench_copy):
    import calibrate
    res, state, _, _ = run_once(bench_copy, "smoke.chat", SEED, 3.0)
    done = [(rid, l) for rid, l in state.lives.items()
            if l.served is not None]
    check = state.conf["check"]
    sample = R.pick_sample(done, SEED, check)
    got = calibrate.readings(state.m, SEED, sample, check)
    print(got)
    assert got["program_correct"] and not got["control_correct"]
    for k, limit in check["limits"].items():
        prog, ctrl = got["program"][k], got["control"][k]
        assert prog == pytest.approx(res["check"][k]["value"])
        assert prog <= limit < ctrl, k
        assert np.isfinite(ctrl)
