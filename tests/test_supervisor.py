"""Job-level fault tolerance (mxnet_tpu/resilience: supervisor.py,
jobstate.py + the state_dict/load_state surfaces it rides on).

Covers: TrainJobState serialization (int/str key fidelity), iterator
and DataLoader resume positions, EvalMetric accumulator state,
mid-epoch bit-exact fit resume (params, RNG, guard counters, metric),
the optimizer-state mismatch satellite, chaos kill/hang injection
points, the heartbeat/watchdog supervisor (dead vs hung children,
flight records, bounded restarts), and the events.jsonl monotone-seq
contract across a restart.  The end-to-end crash-anywhere proof runs
as its own CI stage (ci/crash_anywhere_drill.py)."""

import json
import os
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import resilience
from mxnet_tpu.io import NDArrayIter, PrefetchingIter, ResizeIter
from mxnet_tpu.resilience import (CheckpointManager, StateMismatchError,
                                  TrainJobState, chaos)
from mxnet_tpu.resilience import supervisor as sup
from mxnet_tpu.resilience.jobstate import decode_keyed, encode_keyed

PY = sys.executable


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    chaos.reset()
    resilience.clear_preemption()
    monkeypatch.delenv("MXNET_HEARTBEAT_FILE", raising=False)
    sup.reset_heartbeat()
    yield
    chaos.reset()
    resilience.clear_preemption()
    sup.reset_heartbeat()


# ---------------------------------------------------------------------------
# model/data helpers (same tiny MLP as test_resilience)
# ---------------------------------------------------------------------------

def _mlp(dropout=False):
    data = sym.var("data")
    net = sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    if dropout:
        net = sym.Dropout(net, p=0.5, name="drop")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _toy_iter(n=64, batch=16, shuffle=False):
    rng = np.random.RandomState(0)
    X = rng.randn(n, 8).astype(np.float32)
    Y = rng.randint(0, 4, n).astype(np.float32)
    return NDArrayIter(X, Y, batch_size=batch, shuffle=shuffle)


def _params_bytes(mod):
    args, auxs = mod.get_params()
    table = {}
    for k, v in list(args.items()) + list(auxs.items()):
        table[k] = np.asarray(v.asnumpy()).tobytes()
    return table


# ---------------------------------------------------------------------------
# TrainJobState serialization
# ---------------------------------------------------------------------------

def test_jobstate_roundtrips_int_and_str_keys():
    js = TrainJobState(
        epoch=2, nbatch=5,
        module={"opt_counts": {0: 7, 1: 7, "named": 3},
                "rng": {"shape": [2], "data": [0, 99]}},
        metric={"metric": "Accuracy",
                "state": {"num_inst": 10, "sum_metric": 4.25,
                          "per_class": {0: 1, 1: 2, "other": 3}}},
        data={"type": "NDArrayIter", "cursor": 80, "idx": None})
    back = TrainJobState.from_bytes(js.to_bytes())
    assert back.epoch == 2 and back.nbatch == 5
    counts = back.module["opt_counts"]
    # int keys stay ints, str keys stay strs — plain JSON would have
    # silently stringified the indices
    assert counts == {0: 7, 1: 7, "named": 3}
    assert set(map(type, counts)) == {int, str}
    per_class = back.metric["state"]["per_class"]
    assert per_class == {0: 1, 1: 2, "other": 3}
    assert back.metric["state"]["sum_metric"] == 4.25
    assert back.data["cursor"] == 80


def test_jobstate_rejects_unknown_version():
    blob = json.dumps({"version": 99, "epoch": 0, "nbatch": 0}).encode()
    with pytest.raises(ValueError, match="version"):
        TrainJobState.from_bytes(blob)


def test_keyed_encoding_nested():
    obj = {1: {2: "a"}, "x": [{"y": {3: 4}}]}
    assert decode_keyed(encode_keyed(obj)) == obj


def test_jobstate_rides_checkpoint_manifest(tmp_path):
    """restore_latest() hands back the TrainJobState, checksummed like
    every other checkpoint file."""
    mgr = CheckpointManager(str(tmp_path / "job"))
    js = TrainJobState(epoch=1, nbatch=3,
                       module={"opt_counts": {0: 4}, "step_seq": 7})
    mgr.save_checkpoint(1, arg_params={"w": nd.zeros((2,))},
                        job_state=js)
    rec = mgr.restore_latest()
    back = rec.load_job_state()
    assert back.nbatch == 3 and back.module["opt_counts"] == {0: 4}
    # corruption of the jobstate file is caught by the manifest
    with open(rec.jobstate_path, "r+b") as f:
        f.write(b"X")
    assert mgr.restore_latest() is None


def test_checkpoint_without_jobstate_loads_as_none(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "old"))
    mgr.save_checkpoint(0, arg_params={"w": nd.zeros((2,))})
    assert mgr.restore_latest().load_job_state() is None


# ---------------------------------------------------------------------------
# iterator / DataLoader / metric resume state
# ---------------------------------------------------------------------------

def _collect(it, n):
    out = []
    for _ in range(n):
        out.append(np.asarray(it.next().data[0].asnumpy()))
    return out


def test_ndarrayiter_state_roundtrip_shuffled():
    it = _toy_iter(shuffle=True)
    _collect(it, 2)
    st = it.state_dict()
    rest = _collect(it, 2)
    it2 = _toy_iter(shuffle=True)        # different fresh permutation
    it2.load_state(st)
    rest2 = _collect(it2, 2)
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_prefetchingiter_state_counts_consumed_not_prefetched(depth):
    # consumed-batch accounting must be ring-depth invariant: a deeper
    # ring runs the producer further AHEAD of the consumer, but the
    # resume cursor counts only batches DELIVERED
    it = PrefetchingIter(_toy_iter(), prefetch_depth=depth)
    _collect(it, 2)
    st = it.state_dict()
    assert st["consumed"] == 2
    rest = _collect(it, 2)
    it2 = PrefetchingIter(_toy_iter(), prefetch_depth=depth)
    it2.load_state(st)
    rest2 = _collect(it2, 2)
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_device_prefetcher_state_counts_consumed_not_prefetched(depth):
    # same contract one layer lower: the device-resident ring holds
    # depth prefetched-and-transferred batches, none of which may leak
    # into the resume cursor
    from mxnet_tpu.io import DevicePrefetcher
    it = DevicePrefetcher(_toy_iter(), depth=depth)
    _collect(it, 2)
    st = it.state_dict()
    assert st["consumed"] == 2
    rest = _collect(it, 2)
    it2 = DevicePrefetcher(_toy_iter(), depth=depth)
    it2.load_state(st)
    rest2 = _collect(it2, 2)
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


def test_resizeiter_state_roundtrip():
    it = ResizeIter(_toy_iter(), size=3)
    it.next()
    st = it.state_dict()
    a = np.asarray(it.next().data[0].asnumpy())
    it2 = ResizeIter(_toy_iter(), size=3)
    it2.load_state(st)
    b = np.asarray(it2.next().data[0].asnumpy())
    np.testing.assert_array_equal(a, b)


def test_iterator_state_type_mismatch_raises():
    it = _toy_iter()
    with pytest.raises(ValueError, match="captured from"):
        it.load_state({"type": "LibSVMIter", "cursor": 0})


def test_dataloader_state_resumes_shuffle_order_and_cursor():
    from mxnet_tpu.gluon.data import DataLoader
    data = [np.full((2,), i, np.float32) for i in range(32)]
    dl = DataLoader(data, batch_size=4, shuffle=True)
    it = iter(dl)
    seen = [np.asarray(next(it).asnumpy()) for _ in range(3)]
    st = dl.state_dict()
    assert st["cursor"] == 3
    rest = [np.asarray(b.asnumpy()) for b in it]
    dl2 = DataLoader(data, batch_size=4, shuffle=True)
    dl2.load_state(st)
    rest2 = [np.asarray(b.asnumpy()) for b in dl2]
    assert len(rest) == len(rest2) == 5
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


def test_dataloader_rollover_resume_keeps_leftovers():
    """last_batch='rollover' epochs begin with the previous epoch's
    leftovers; a mid-epoch resume must regenerate the SAME epoch
    stream — leftovers included — not a freshly-offset one."""
    from mxnet_tpu.gluon.data import DataLoader
    data = [np.full((1,), i, np.float32) for i in range(10)]
    np.random.seed(77)
    dl = DataLoader(data, batch_size=4, shuffle=True,
                    last_batch="rollover")
    list(iter(dl))                            # epoch 0: leaves leftovers
    it = iter(dl)                             # epoch 1 starts with them
    first = np.asarray(next(it).asnumpy())
    st = dl.state_dict()
    rest = [np.asarray(b.asnumpy()) for b in it]
    np.random.seed(77)
    dl2 = DataLoader(data, batch_size=4, shuffle=True,
                     last_batch="rollover")
    list(iter(dl2))                           # epoch 0 consumed
    dl2.load_state(st)                        # resume mid-epoch 1
    rest2 = [np.asarray(b.asnumpy()) for b in dl2]
    assert len(rest) == len(rest2)
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


def test_metric_state_roundtrip_composite_and_keyed():
    m = mx.metric.CompositeEvalMetric(["acc", "mse"])
    m.metrics[0].num_inst = 12
    m.metrics[0].sum_metric = 5.0
    m.metrics[1].num_inst = 3
    st = m.state_dict()
    m2 = mx.metric.CompositeEvalMetric(["acc", "mse"])
    m2.load_state(st)
    assert m2.metrics[0].num_inst == 12
    assert m2.metrics[0].sum_metric == 5.0
    assert m2.metrics[1].num_inst == 3
    with pytest.raises(ValueError, match="captured from"):
        mx.metric.create("mse").load_state(
            mx.metric.create("acc").state_dict())


# ---------------------------------------------------------------------------
# mid-epoch bit-exact resume through fit()
# ---------------------------------------------------------------------------

def _run_fit(mod, it, mgr=None, resume=None, callback=None, epochs=2,
             every=None):
    mod.fit(it, num_epoch=epochs, optimizer="sgd", eval_metric="acc",
            optimizer_params={"learning_rate": 0.1},
            checkpoint_manager=mgr, resume_from=resume,
            checkpoint_every_n_batches=every,
            batch_end_callback=callback)


def test_fit_resume_mid_epoch_bit_exact(tmp_path):
    """Preempt mid-epoch, resume with resume_from: every subsequent
    (epoch, nbatch, params) triple — dropout masks AND shuffle orders
    included, through an epoch boundary AFTER the resume (the shuffle
    stream must realign, not just the current permutation) — matches
    the uninterrupted run bit-for-bit, and no batch is replayed or
    skipped."""
    def shuffled_iter():
        np.random.seed(123)      # NDArrayIter draws its shuffle seed
        return _toy_iter(shuffle=True)

    log1 = []
    mx.random.seed(11)
    m1 = mx.Module(_mlp(dropout=True), context=mx.cpu())
    _run_fit(m1, shuffled_iter(), epochs=3,
             callback=lambda p: log1.append(
                 (p.epoch, p.nbatch,
                  sorted(_params_bytes(m1).items()))))

    log2 = []
    mx.random.seed(11)
    mgr = CheckpointManager(str(tmp_path / "mid"))
    m2 = mx.Module(_mlp(dropout=True), context=mx.cpu())
    chaos.configure(preempt_at_batch=6)      # epoch 1, batch 1
    _run_fit(m2, shuffled_iter(), mgr=mgr, epochs=3,
             callback=lambda p: log2.append(
                 (p.epoch, p.nbatch,
                  sorted(_params_bytes(m2).items()))))
    chaos.reset()
    resilience.clear_preemption()

    rec = mgr.restore_latest()
    job = rec.load_job_state()
    assert job.epoch == 1 and job.nbatch == 1
    m3 = mx.Module(_mlp(dropout=True), context=mx.cpu())
    _run_fit(m3, shuffled_iter(), mgr=mgr, resume=rec, epochs=3,
             callback=lambda p: log2.append(
                 (p.epoch, p.nbatch,
                  sorted(_params_bytes(m3).items()))))
    assert [(e, b) for e, b, _ in log2] == \
        [(e, b) for e, b, _ in log1]          # no replay, no skip
    assert log1 == log2                       # bit-exact params


def test_fit_resume_guard_counters_survive(tmp_path):
    """guard_skipped_steps and the consecutive-bad-step counter ride
    the job state: a restart must not forget how close the job was to
    its divergence limit."""
    mgr = CheckpointManager(str(tmp_path / "guard"))
    mx.random.seed(3)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.set_nonfinite_guard(max_consecutive=0)
    chaos.configure(nan_grads_at_step=1, preempt_at_batch=3)
    _run_fit(mod, _toy_iter(), mgr=mgr)
    chaos.reset()
    resilience.clear_preemption()
    assert mod.nonfinite_skipped == 1
    assert mod._guard_consec == 0             # a good step followed

    rec = mgr.restore_latest()
    step_at_capture = rec.load_job_state().module["step_seq"]
    mod2 = mx.Module(_mlp(), context=mx.cpu())
    mod2.set_nonfinite_guard(max_consecutive=0)
    chaos.configure(preempt_at_batch=1)
    _run_fit(mod2, _toy_iter(), mgr=mgr, resume=rec)
    chaos.reset()
    resilience.clear_preemption()
    assert mod2.nonfinite_skipped >= 1        # restored, not reset
    assert mod2._step_seq > step_at_capture


def test_fit_resume_params_only_checkpoint_advances_epoch(tmp_path):
    """A pre-job-state (params-only) checkpoint resumes at the NEXT
    epoch — never re-training epoch 0 over the restored weights."""
    mgr = CheckpointManager(str(tmp_path / "po"))
    mx.random.seed(9)
    m1 = mx.Module(_mlp(), context=mx.cpu())
    m1.fit(_toy_iter(), num_epoch=1, optimizer="sgd")
    mgr.save_module(m1, 0)                    # no job_state
    seen = []
    m2 = mx.Module(_mlp(), context=mx.cpu())
    _run_fit(m2, _toy_iter(), mgr=mgr, resume="latest", epochs=3,
             callback=lambda p: seen.append(p.epoch))
    assert set(seen) == {1, 2}


def test_fit_resume_from_epoch_boundary(tmp_path):
    """An epoch-end checkpoint's job state points at the NEXT epoch;
    resuming trains exactly the remaining epochs."""
    mgr = CheckpointManager(str(tmp_path / "eb"))
    mx.random.seed(5)
    m1 = mx.Module(_mlp(), context=mx.cpu())
    _run_fit(m1, _toy_iter(), mgr=mgr, epochs=1)
    job = mgr.restore_latest().load_job_state()
    assert job.epoch == 1 and job.nbatch == -1

    seen = []
    m2 = mx.Module(_mlp(), context=mx.cpu())
    _run_fit(m2, _toy_iter(), mgr=mgr, resume="latest", epochs=3,
             callback=lambda p: seen.append((p.epoch, p.nbatch)))
    assert {e for e, _ in seen} == {1, 2}     # epoch 0 not replayed


def test_checkpoint_every_n_batches_commits_resumable_state(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "per"))
    mx.random.seed(2)
    mod = mx.Module(_mlp(), context=mx.cpu())
    _run_fit(mod, _toy_iter(), mgr=mgr, epochs=1, every=2)
    job = mgr.restore_latest().load_job_state()
    # 4 batches/epoch: the last PER-BATCH state was after batch 3, the
    # epoch-end save then supersedes it — both must be committed forms
    assert job is not None
    assert job.nbatch in (-1, 1, 3)


# ---------------------------------------------------------------------------
# satellite: load_optimizer_states validation
# ---------------------------------------------------------------------------

def _fitted_module(tmp_path, optimizer="sgd", **opt_params):
    mod = mx.Module(_mlp(), context=mx.cpu())
    it = _toy_iter()
    opt_params.setdefault("learning_rate", 0.1)
    mod.fit(it, num_epoch=1, optimizer=optimizer,
            optimizer_params=opt_params)
    return mod


def test_load_optimizer_states_rejects_wrong_class(tmp_path):
    m1 = _fitted_module(tmp_path, optimizer="adam",
                        learning_rate=0.001)
    path = str(tmp_path / "opt.states")
    m1.save_optimizer_states(path)
    m2 = _fitted_module(tmp_path, optimizer="sgd")
    with pytest.raises(StateMismatchError, match="Adam.*SGD"):
        m2.load_optimizer_states(path)


def test_load_optimizer_states_rejects_hyper_mutation(tmp_path):
    m1 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.9)
    path = str(tmp_path / "opt.states")
    m1.save_optimizer_states(path)
    m2 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.5)
    with pytest.raises(StateMismatchError, match="momentum"):
        m2.load_optimizer_states(path)


def test_load_optimizer_states_reinit_knob(tmp_path, monkeypatch,
                                           caplog):
    m1 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.9)
    path = str(tmp_path / "opt.states")
    m1.save_optimizer_states(path)
    m2 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.5)
    monkeypatch.setenv("MXNET_OPTSTATE_MISMATCH", "reinit")
    import logging
    with caplog.at_level(logging.WARNING):
        m2.load_optimizer_states(path)       # warns, does not raise
    assert any("re-initializing" in r.message for r in caplog.records)
    assert m2._updater.states == {}


def test_load_optimizer_states_matching_blob_roundtrips(tmp_path):
    m1 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.9)
    path = str(tmp_path / "opt.states")
    m1.save_optimizer_states(path)
    m2 = _fitted_module(tmp_path, optimizer="sgd", momentum=0.9)
    m2.load_optimizer_states(path)
    assert set(m2._updater.states) == set(m1._updater.states)


def test_legacy_headerless_blob_still_loads(tmp_path):
    import pickle
    m = _fitted_module(tmp_path, optimizer="sgd", momentum=0.9)
    legacy = pickle.dumps({0: ("raw", None)})
    m._apply_updater_states(legacy)          # vacuous validation
    assert 0 in m._updater.states


# ---------------------------------------------------------------------------
# chaos kill/hang injection points
# ---------------------------------------------------------------------------

def test_chaos_kill_at_step_exits_at_exact_step(monkeypatch):
    exits = []
    monkeypatch.setattr(chaos, "_exit",
                        lambda code: (_ for _ in ()).throw(
                            SystemExit(code)))
    chaos.configure(kill_at_step=2)
    mx.random.seed(1)
    mod = mx.Module(_mlp(), context=mx.cpu())
    it = _toy_iter()
    with pytest.raises(SystemExit) as e:
        mod.fit(it, num_epoch=2, optimizer="sgd")
    assert e.value.code == 137
    assert mod._step_seq == 2                # steps 0,1 trained
    assert chaos.fired("kill_at_step") == 1


def test_chaos_kill_respects_resumed_step_seq(monkeypatch):
    """A restarted job resumed PAST the armed step is not re-killed —
    the comparison is against the resumable global step."""
    monkeypatch.setattr(chaos, "_exit",
                        lambda code: (_ for _ in ()).throw(
                            SystemExit(code)))
    chaos.configure(kill_at_step=1)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.bind([("data", (16, 8))], [("softmax_label", (16,))])
    mod.init_params()
    mod.init_optimizer()
    mod._step_seq = 5                         # "resumed" beyond K
    batch = next(iter(_toy_iter()))
    mod.forward_backward_update(batch)        # no kill
    assert chaos.fired("kill_at_step") == 0


def test_chaos_hang_at_step_is_interruptible(monkeypatch):
    class _Stop(Exception):
        pass
    ticks = []

    def fake_sleep(s):
        ticks.append(s)
        if len(ticks) >= 3:
            raise _Stop()
    monkeypatch.setattr(chaos, "_hang_sleep", fake_sleep)
    chaos.configure(hang_at_step=0)
    mod = mx.Module(_mlp(), context=mx.cpu())
    with pytest.raises(_Stop):
        mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd")
    assert len(ticks) == 3
    assert chaos.fired("hang_at_step") == 1


# ---------------------------------------------------------------------------
# heartbeat + supervisor
# ---------------------------------------------------------------------------

def test_heartbeat_noop_without_env():
    assert sup.heartbeat() == 0


def test_heartbeat_ticks_and_reads(tmp_path, monkeypatch):
    path = str(tmp_path / "hb")
    monkeypatch.setenv("MXNET_HEARTBEAT_FILE", path)
    assert sup.read_heartbeat(path) is None
    assert sup.heartbeat() == 1
    assert sup.heartbeat() == 2
    assert sup.read_heartbeat(path) == 2


def test_fit_ticks_heartbeat(tmp_path, monkeypatch):
    path = str(tmp_path / "hb")
    monkeypatch.setenv("MXNET_HEARTBEAT_FILE", path)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd")
    assert sup.read_heartbeat(path) == 4      # one tick per batch


_CHILD_DIES_THEN_OK = r'''
import os, sys
marker = os.path.join(os.environ["T_DIR"], "attempts")
with open(marker, "a") as f:
    f.write("x")
n = len(open(marker).read())
if n < 3:
    os._exit(9)
open(os.path.join(os.environ["T_DIR"], "done"), "w").write("ok")
'''


def test_supervisor_restarts_dead_child_until_success(tmp_path):
    s = sup.Supervisor([PY, "-c", _CHILD_DIES_THEN_OK],
                       workdir=str(tmp_path), timeout=30,
                       max_restarts=5, env={"T_DIR": str(tmp_path)},
                       base_delay=0.01, max_delay=0.02,
                       poll_interval=0.02)
    res = s.run()
    assert res.ok and res.deaths == 2 and res.hangs == 0
    assert res.attempts == 3
    assert os.path.exists(str(tmp_path / "done"))


def test_supervisor_relative_workdir_heartbeat_resolves(tmp_path,
                                                        monkeypatch):
    """The child runs with cwd=workdir; a RELATIVE workdir must still
    hand it an absolute heartbeat path (workdir/workdir/heartbeat was
    the failure mode)."""
    monkeypatch.chdir(tmp_path)
    s = sup.Supervisor([PY, "-c", "pass"], workdir="job", timeout=30,
                       max_restarts=0, poll_interval=0.02)
    assert os.path.isabs(s.heartbeat_path)
    assert s.heartbeat_path == str(tmp_path / "job" / "heartbeat")
    assert s.run().ok


def test_supervisor_gives_up_when_budget_spent(tmp_path):
    s = sup.Supervisor([PY, "-c", "import os; os._exit(7)"],
                       workdir=str(tmp_path), timeout=30,
                       max_restarts=1, base_delay=0.01, max_delay=0.02,
                       poll_interval=0.02)
    res = s.run()
    assert not res.ok and res.exit_code == 7
    assert res.deaths == 2                    # initial + 1 restart


_CHILD_HANGS = r'''
import os, sys, time
sys.path.insert(0, os.environ["T_REPO"])
from mxnet_tpu.resilience import supervisor as sup
marker = os.path.join(os.environ["T_DIR"], "attempts")
with open(marker, "a") as f:
    f.write("x")
sup.heartbeat()
if len(open(marker).read()) < 2:
    while True:            # heartbeat never advances again
        time.sleep(0.2)
open(os.path.join(os.environ["T_DIR"], "done"), "w").write("ok")
'''


def test_supervisor_detects_hang_dumps_flight_record(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # startup slack is 4x the timeout and the child's package import
    # alone takes 3.4-3.9 s: 1.0 left no margin under a loaded suite
    s = sup.Supervisor([PY, "-c", _CHILD_HANGS],
                       workdir=str(tmp_path), timeout=2.0,
                       max_restarts=2,
                       env={"T_DIR": str(tmp_path), "T_REPO": repo},
                       base_delay=0.01, max_delay=0.02,
                       poll_interval=0.05, grace=1.0)
    res = s.run()
    assert res.ok and res.hangs == 1 and res.deaths == 0
    assert len(res.flight_records) == 1
    with open(res.flight_records[0]) as f:
        flight = json.load(f)
    assert flight["reason"] == "hang"
    assert flight["watchdog_timeout_s"] == 2.0
    # faulthandler stacks were dumped by the hung child
    assert flight["stacks_path"] is not None
    assert os.path.getsize(flight["stacks_path"]) > 0


# ---------------------------------------------------------------------------
# events.jsonl monotone seq across a restart
# ---------------------------------------------------------------------------

def test_events_seq_continues_across_writer_restart(tmp_path,
                                                    monkeypatch):
    from mxnet_tpu.observability import events
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXNET_OBS", "all")
    events.configure(path=path, rate=0)
    try:
        events.emit("supervisor", action="start")
        events.emit("checkpoint", action="commit")
        # "restart": a fresh writer (new process in real life) must
        # continue the seq, not restart at 1
        events.configure(path=path, rate=0)
        events.emit("supervisor", action="restart")
        events.emit("watchdog", action="hang_killed")
        recs = events.read_events(path)
        seqs = [r["seq"] for r in recs]
        assert seqs == [1, 2, 3, 4]
        assert recs[2]["ev"] == "supervisor"
    finally:
        events.configure()
        monkeypatch.delenv("MXNET_OBS_PATH", raising=False)


def test_events_reopen_resyncs_parent_writer(tmp_path, monkeypatch):
    from mxnet_tpu.observability import events
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("MXNET_OBS", "all")
    events.configure(path=path, rate=0)
    try:
        events.emit("supervisor", action="start")     # seq 1
        # another process appends with higher seqs behind our back
        with open(path, "a") as f:
            f.write(json.dumps({"ts": 0, "ev": "x", "pid": 0,
                                "seq": 9}) + "\n")
        events.reopen()
        events.emit("supervisor", action="restart")   # must be seq 10
        assert events.read_events(path)[-1]["seq"] == 10
    finally:
        events.configure()
        monkeypatch.delenv("MXNET_OBS_PATH", raising=False)


# ---------------------------------------------------------------------------
# preemption coverage of the other training entry points (satellite)
# ---------------------------------------------------------------------------

def test_model_fit_legacy_entry_is_preemption_safe(tmp_path):
    from mxnet_tpu import model as model_mod
    mgr = CheckpointManager(str(tmp_path / "legacy"))
    seen = []
    chaos.configure(preempt_at_batch=2)
    mod = model_mod.fit(_mlp(), _toy_iter(), num_epoch=5,
                        ctx=mx.cpu(), optimizer="sgd",
                        checkpoint_manager=mgr,
                        batch_end_callback=lambda p: seen.append(
                            p.nbatch))
    assert seen == [0, 1]
    assert mgr.restore_latest() is not None
    assert mod.binded and mod.params_initialized


def test_parallel_trainer_fit_is_preemption_safe(tmp_path):
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel import ParallelTrainer
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize()
    trainer = ParallelTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                              optimizer="sgd",
                              optimizer_params={"learning_rate": 0.1})
    prefix = str(tmp_path / "pt")
    seen = []
    chaos.configure(preempt_at_batch=2)
    trainer.fit(_toy_iter(), num_epoch=3, checkpoint_prefix=prefix,
                batch_end_callback=lambda e, b, l: seen.append((e, b)))
    assert seen == [(0, 0), (0, 1)]
    assert os.path.exists(prefix + "-0000.params")
    assert trainer._num_update == 2
