"""The decode loop's step behind one transfer each way
(``scheduler._ControlledStep``): the device keeps the step's control
(page tables, positions, forced tokens, live rows) and advances it
itself, the host sends its picture only where that differs from the
device's copy, and a step's results come back as one array.

Every scenario runs twice on the CPU: as the loop runs it, and with the
picture sent every step, as the loop sent its control before it kept a
copy on the device.  The tokens (and the failures) of the two runs are
bit-identical, and on every step that sent nothing the device's copy
held exactly the host's picture.  The scenarios cover each step kind a
cell runs: plain rows, two page classes with window moves, the latent
class, block rows; and admission mid-stream, retirement, forced tokens
on resume, a quarantined slot and the supervisor's restart.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserver import faults
from tpuserver import scheduler as scheduler_mod
from tpuserver.models import llama
from tpuserver.scheduler import DecodeScheduler

PLAIN = llama.tiny(vocab=512)
WINDOW = dataclasses.replace(llama.tiny_afmoe(vocab=512),
                             attn_impl="pallas", decode_impl="pallas")
LATENT = dataclasses.replace(llama.tiny_deepseek(vocab=512),
                             attn_impl="pallas")
BLOCKS = llama.tiny_sdar(vocab=256)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def audit(monkeypatch):
    """Every dispatch through ``_ControlledStep``: counted as sent or
    kept; a kept one checked against the host's picture; with
    ``always`` set, sent every step (the device's copy ignored)."""
    log = {"sent": 0, "kept": 0, "always": False}
    dispatch = scheduler_mod._ControlledStep.__call__

    def audited(self, params, pages, logits, picture, held):
        if log["always"]:
            held = None
        elif held is not None and np.array_equal(picture, held[1]):
            np.testing.assert_array_equal(np.asarray(held[0]), picture)
        out = dispatch(self, params, pages, logits, picture, held)
        log["sent" if out[-1] else "kept"] += 1
        return out

    monkeypatch.setattr(scheduler_mod._ControlledStep, "__call__", audited)
    return log


def _scheduler(cfg, max_seq, slots=2, **kw):
    fns = llama.make_scheduler_fns(cfg, max_seq, slots, page_size=16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return DecodeScheduler(fns, params, slots, max_seq, **kw)


def _prompt(n, seed, vocab=200):
    return np.random.default_rng(seed).integers(3, vocab, n).astype(np.int32)


def _outcome(stream):
    """The stream's pairs (tokens and logprobs, or a block model's
    blocks), and the name of what it raised, if it did."""
    got = []
    try:
        for pair in stream:
            got.append(repr(pair))
    except Exception as e:  # noqa: BLE001 — the failure is the outcome
        return got, type(e).__name__
    return got, None


def _run(cfg, max_seq, asks, slots=2, **kw):
    """Submit every ask ``(prompt, max_tokens, submit kwargs)`` at once
    (more asks than slots: the rest are admitted as slots retire) and
    read each stream to its end."""
    sched = _scheduler(cfg, max_seq, slots, **kw)
    try:
        streams = [sched.submit(p, n, **extra) for p, n, extra in asks]
        return [_outcome(s) for s in streams], sched.stats()
    finally:
        sched.close()


def _both(audit, scenario):
    """The scenario as the loop runs it, then sent every step: the same
    outcomes, and steps that sent nothing in the first."""
    kept, stats = scenario()
    assert audit["kept"] > 0, audit
    assert stats["control_uploads"] == audit["sent"]
    audit["always"] = True
    sent, _ = scenario()
    assert sent == kept
    return kept, stats


def test_admission_and_retirement_mid_stream(audit):
    asks = [(_prompt(7, 1), 12, {}), (_prompt(5, 2), 4, {}),
            (_prompt(9, 3), 8, {}), (_prompt(6, 4), 3, {})]
    outcomes, stats = _both(audit, lambda: _run(PLAIN, 64, asks))
    assert [len(t) for t, err in outcomes] == [12, 4, 8, 3]
    assert all(err is None for _, err in outcomes)
    assert stats["admitted"] == 4


def test_forced_tokens_on_resume(audit):
    """A generation resumed over a parked cache replays its new prompt
    as forced tokens, one a step, beside a row that decodes freely."""

    def scenario():
        sched = _scheduler(PLAIN, 64)
        try:
            parked = []
            first = _prompt(6, 5)
            done = list(sched.submit(first, 5, on_finish=parked.append))
            resumed = sched.submit(_prompt(4, 6), 6, resume_cache=parked[0],
                                   resume_pos=len(first) + len(done))
            beside = sched.submit(_prompt(8, 7), 10)
            return [_outcome(resumed), _outcome(beside)], sched.stats()
        finally:
            sched.close()

    outcomes, _ = _both(audit, scenario)
    assert [len(t) for t, _ in outcomes] == [6, 10]


def test_quarantined_slot(audit):
    def scenario():
        faults.install("scheduler.step", mode="nan", times=1, delay=0)
        return _run(PLAIN, 64, [(_prompt(5, 8), 6, {}), (_prompt(7, 9), 6, {})])

    outcomes, stats = _both(audit, scenario)
    assert [err for _, err in outcomes] == ["SlotQuarantined", None]
    assert stats["quarantined"] == 1


def test_supervisor_restart(audit):
    """The loop dies mid-generation; its successor starts with no copy
    on the device, re-admits both streams and sends its first step."""

    def scenario():
        faults.install("scheduler.step", mode="raise", times=1, skip=4)
        return _run(PLAIN, 64, [(_prompt(5, 10), 9, {}),
                                (_prompt(6, 11), 7, {})])

    outcomes, stats = _both(audit, scenario)
    assert [(len(t), err) for t, err in outcomes] == [(9, None), (7, None)]
    assert stats["restarts"] == 1


def test_two_page_classes_with_window_moves(audit):
    """Two page classes: rows past the window hand ring entries back as
    they go, and such a step sends its picture."""
    asks = [(_prompt(40, 12), 20, {}), (_prompt(20, 13), 30, {}),
            (_prompt(9, 14), 6, {})]
    outcomes, stats = _both(audit, lambda: _run(WINDOW, 256, asks))
    assert [len(t) for t, _ in outcomes] == [20, 30, 6]
    assert stats["window_skipped_tokens"] > 0


def test_latent_class(audit):
    asks = [(_prompt(12, 15), 10, {}), (_prompt(7, 16), 4, {}),
            (_prompt(5, 17), 6, {})]
    outcomes, _ = _both(audit, lambda: _run(LATENT, 128, asks))
    assert [len(t) for t, _ in outcomes] == [10, 4, 6]


def test_block_rows(audit):
    asks = [(_prompt(9, 18), 12, {"denoising_steps": 4}),
            (_prompt(6, 19), 8, {"denoising_steps": 2}),
            (_prompt(7, 20), 5, {"denoising_steps": 1})]
    outcomes, stats = _both(audit, lambda: _run(BLOCKS, 64, asks))
    assert all(err is None for _, err in outcomes)
    assert stats["tokens"] == 12 + 8 + 5


def test_a_lone_stream_uploads_once(audit):
    """No slot changes after the admission: the first step sends the
    picture and every later one runs on the device's copy."""
    outcomes, stats = _run(PLAIN, 64, [(_prompt(6, 21), 20, {})])
    assert len(outcomes[0][0]) == 20
    assert stats["control_uploads"] == 1 == audit["sent"]
    assert audit["kept"] >= 19


def test_results_come_back_as_one_array():
    """The step's results (tokens, logprobs and a routed configuration's
    counts) pack into one int32 array and unpack to what they were."""
    step = scheduler_mod._ControlledStep(lambda: None, 2, 0, 0)
    tokens = np.array([5, 7], np.int32)
    logps = np.array([-0.25, -1.5], np.float32)
    counts = np.array([4, 9, 3], np.int32)
    step._layout = [(a.shape, a.dtype) for a in (tokens, logps, counts)]
    packed = np.concatenate([tokens, logps.view(np.int32), counts])
    got = step.unpack(packed)
    for want, have in zip((tokens, logps, counts), got):
        assert have.dtype == want.dtype
        np.testing.assert_array_equal(have, want)
    # the picture: tables, then each vector as a column, floats by bits
    picture = step.picture(np.full((2, 2), 9, np.int32), None,
                           np.array([1, 2], np.int32),
                           np.array([True, False]),
                           np.array([0.5, 1.0], np.float32))
    assert picture.dtype == np.int32 and picture.shape == (2, 5)
    assert picture[:, 4].view(np.float32).tolist() == [0.5, 1.0]
    assert jnp.asarray(picture).shape == (2, 5)
