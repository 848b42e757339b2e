"""Program spans (``repro.tracing``) on the training hot path: a profiled,
pipelined ``CTRTrainer`` run with device ingest on the CPU shows every span
once per call, nested in its stage's span, with its attributes; the
unprofiled run trains the same and keeps the same record."""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.configs.ctr_models import CTRConfig
from repro.core.node import Cluster
from repro.data.synthetic_ctr import SyntheticCTRStream
from repro.train.trainer import CTRTrainer, TrainerConfig

CFG = CTRConfig(name="ctr-trace", n_sparse_keys=3_000, nnz_per_example=16, emb_dim=4,
                n_slots=8, mlp_hidden=(16, 8), batch_size=64, minibatches_per_batch=2)
N_BATCHES = 8

# span -> the stage whose job it runs in
STAGE_OF = {
    "ps.push": "pull_push", "ps.keys": "pull_push", "ps.pull": "pull_push",
    "ps.conflict_wait": "pull_push", "mem.evict": "pull_push", "ssd.read": "pull_push",
    "ssd.init": "pull_push", "ssd.write": "pull_push", "ssd.compact": "pull_push",
    "ingest.ring_wait": "ingest", "ingest.extract": "ingest", "train.readback": "train",
}
# spans of the push side, which the run's final drain also makes, on the
# caller's thread after the pipeline has ended
PUSH_SIDE = {"ps.push", "mem.evict", "ssd.write", "ssd.compact"}
BATCH_ATTR = {"ps.push", "ps.keys", "ps.pull", "ps.conflict_wait", "ingest.extract",
              "train.readback"}


def _train(tmp_path, tag):
    """A run in the storage-bound regime at toy size: the MEM-PS holds a
    fraction of the keys and flushes small batches; each SSD-PS starts with
    a stale file (keys the stream never draws, written twice), which its
    first flush in the run compacts; a slow train stage under a 3-deep
    staging ring keeps older batches in flight while later ones pull, so
    pulls wait on them."""
    tracing.clear()
    cl = Cluster(2, str(tmp_path / tag), dim=2 * CFG.emb_dim, cache_capacity=600,
                 file_capacity=16, init_cols=CFG.emb_dim)
    unused = np.arange(CFG.n_sparse_keys, CFG.n_sparse_keys + 16, dtype=np.uint64)
    for nd in cl.nodes:
        nd.mem.flush_batch = 32
        nd.ssd.auto_compact = False
        for _ in range(2):
            nd.ssd.write_batch(unused, nd.ssd.init_rows(unused))
        nd.ssd.auto_compact = True
    tr = CTRTrainer(CFG, cl, TrainerConfig(ingest=True, staging_depth=3))
    train = tr._stage_train

    def slow_train(item):
        time.sleep(0.05)
        return train(item)

    tr._stage_train = slow_train
    stream = SyntheticCTRStream(CFG.n_sparse_keys, CFG.nnz_per_example, CFG.n_slots,
                                CFG.batch_size, seed=3)
    losses = [r["loss"] for r in tr.run(stream.raw_records(), N_BATCHES)]
    return tr, cl, losses, tracing.recorded()


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tracing")
    plain = _train(tmp, "plain")
    out = str(tmp / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        profiled = _train(tmp, "profiled")
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = []  # [name, start_ns, end_ns, thread line, attrs]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, ln in enumerate(plane.lines):
            for ev in ln.events:
                if ev.name.startswith(tracing.PREFIX):
                    name = ev.name[len(tracing.PREFIX):]
                    events.append([name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   (plane.name, line_no), dict(ev.stats)])
    return plain, profiled, events


def _stage_of(ev, events):
    """The stage span that holds ``ev`` on its thread, or None."""
    for st in events:
        if (st[0].startswith("stage.") and st[3] == ev[3]
                and st[1] <= ev[1] and ev[2] <= st[2]):
            return st
    return None


def test_every_span_nested_in_its_stage_with_attrs(runs):
    _, _, events = runs
    names = {e[0] for e in events}
    for stage in ("read", "ingest", "pull_push", "transfer", "train"):
        jobs = sorted(e[4]["job"] for e in events if e[0] == f"stage.{stage}")
        assert jobs == list(range(N_BATCHES)), stage
    assert set(STAGE_OF) <= names, set(STAGE_OF) - names
    for ev in events:
        if ev[0] not in STAGE_OF:
            continue
        st = _stage_of(ev, events)
        if st is None:
            assert ev[0] in PUSH_SIDE, ev  # the final drain's pushes
        else:
            assert st[0] == "stage." + STAGE_OF[ev[0]], (ev, st)
        if ev[0] in BATCH_ATTR:
            assert 0 <= ev[4]["batch"] < N_BATCHES, ev
    waits = [e for e in events if e[0] == "ps.conflict_wait"]
    assert all(e[4]["holder"] < e[4]["batch"] for e in waits)


def test_children_cover_the_pull_push_stage(runs):
    _, _, events = runs
    stage_ns = covered_ns = 0
    for st in (e for e in events if e[0] == "stage.pull_push"):
        kids = sorted((e[1], e[2]) for e in events
                      if e is not st and e[3] == st[3] and st[1] <= e[1] and e[2] <= st[2])
        end = st[1]
        for s, e in kids:  # union of the children's intervals
            covered_ns += max(0, e - max(s, end))
            end = max(end, e)
        stage_ns += st[2] - st[1]
    assert covered_ns >= 0.9 * stage_ns


def test_spans_per_batch_stay_few(runs):
    _, _, events = runs
    assert len(events) <= 100 * N_BATCHES


def test_unprofiled_run_trains_the_same_and_keeps_its_record(runs):
    (tr_a, cl_a, loss_a, rec_a), (tr_b, cl_b, loss_b, rec_b), events = runs
    assert loss_a == loss_b
    # the in-process record holds every span, profiler or not
    assert {r[0] for r in rec_a} >= {tracing.PREFIX + n for n in STAGE_OF}
    assert sorted(r[0] for r in rec_b) == sorted(tracing.PREFIX + e[0] for e in events)
    for cl, rec in ((cl_a, rec_a), (cl_b, rec_b)):
        ssd = [nd.ssd.stats for nd in cl.nodes]
        spans = lambda name, attr: sum(r[4][attr] for r in rec if r[0] == tracing.PREFIX + name)
        assert spans("ssd.init", "rows") == sum(s.rows_initialized for s in ssd) > 0
        assert spans("ssd.compact", "bytes_read") == sum(s.compaction_bytes_read for s in ssd) > 0
        assert sum(s.compaction_bytes_read for s in ssd) < sum(s.bytes_read for s in ssd)
        found = spans("ssd.read", "rows")
        assert found + spans("ssd.init", "rows") == sum(s.rows_requested for s in ssd)
    keys = np.arange(CFG.n_sparse_keys, dtype=np.uint64)
    np.testing.assert_array_equal(cl_a.pull(keys, pin=False), cl_b.pull(keys, pin=False))


def test_span_records_attrs_set_inside_it():
    tracing.clear()
    with tracing.span("unit", batch=1) as sp:
        sp.set(rows=5)
    [(name, start, dur, thread, attrs)] = tracing.recorded()
    assert name == "hps:unit" and dur >= 0 and attrs == {"batch": 1, "rows": 5}
    tracing.clear()
    assert tracing.recorded() == []


def test_record_under_many_threads():
    """Stage threads end spans while a reader copies the record: no span is
    lost and no copy fails."""
    import sys
    import threading

    tracing.clear()
    n_threads, per_thread = 16, 2_000
    errors = []

    def spans():
        for i in range(per_thread):
            with tracing.span("stress", i=i):
                pass

    def reader():
        try:
            for _ in range(200):
                tracing.recorded()
        except Exception as e:  # a copy that failed mid-append
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=spans) for _ in range(n_threads)]
        threads.append(threading.Thread(target=reader))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert len(tracing.recorded()) == n_threads * per_thread
    tracing.clear()
