"""Failure-domain tests: host-group partitioning, the checksummed scheduler
journal + replay, mesh shrink descriptors / elastic re-mesh, checkpoint
manifest self-healing, shrunk-mesh re-tuning, and the forced-8-device
host-loss drill (survivors token-identical, evacuees re-decode, one
``degraded(mesh(...))`` provenance origin + one ``host_lost`` flight dump
per event)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import artefacts
from repro.launch.mesh import make_mesh
from repro.mesh import strategy as ms
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve import domains
from repro.serve.domains import (FailureDomains, JournalState,
                                 SchedulerJournal, replay)
from repro.serve.engine import ContinuousEngine, Request
from repro.testing import faults


def tiny_cfg(**kw):
    base = dict(name="dom-t", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, dtype="float32",
                remat=False, max_seq=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def dense_model():
    cfg = tiny_cfg()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def mk_requests(cfg, n=3):
    key = jax.random.PRNGKey(5)
    temps = [0.0, 0.9, 0.0, 1.3]
    return [Request(
        prompt=jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (5 + 3 * i,), 0, cfg.vocab),
        max_new_tokens=4 + 3 * i, temperature=temps[i % 4],
        top_k=(5 if i % 4 == 1 else 0)) for i in range(n)]


# ---------------------------------------------------------------------------
# host groups: pure partition/attribution logic
# ---------------------------------------------------------------------------

class TestPartition:
    def test_even_contiguous_split(self):
        assert FailureDomains.partition(8, 2) == ((0, 1, 2, 3), (4, 5, 6, 7))
        assert FailureDomains.partition(8, 4) == ((0, 1), (2, 3), (4, 5),
                                                  (6, 7))
        assert FailureDomains.partition(4, 1) == ((0, 1, 2, 3),)

    def test_uneven_split_rejected(self):
        with pytest.raises(ValueError, match="evenly divide"):
            FailureDomains.partition(8, 3)
        with pytest.raises(ValueError, match="hosts"):
            FailureDomains.partition(8, 0)

    def test_slots_for_full_mesh(self):
        groups = FailureDomains.partition(8, 2)
        alive = [True, True]
        # 8 slots over 8 positions: one slot per position, contiguous
        assert FailureDomains.slots_for(groups, alive, 0, 8) == [0, 1, 2, 3]
        assert FailureDomains.slots_for(groups, alive, 1, 8) == [4, 5, 6, 7]
        # 16 slots over 8 positions: two per position
        assert FailureDomains.slots_for(groups, alive, 1, 16) == list(
            range(8, 16))

    def test_slots_for_after_loss_reranks(self):
        """After host 1 of 4 dies, the surviving positions re-rank and
        host 2's slots shift — attribution must track the live placement."""
        groups = FailureDomains.partition(8, 4)
        alive = [True, False, True, True]
        # positions alive: 0,1 (host0) 4,5 (host2) 6,7 (host3) -> ranks 0..5
        assert FailureDomains.slots_for(groups, alive, 2, 12) == [4, 5, 6, 7]
        assert FailureDomains.slots_for(groups, alive, 1, 12) == []

    def test_slots_for_indivisible_rejected(self):
        groups = FailureDomains.partition(4, 2)
        with pytest.raises(ValueError, match="divisible"):
            FailureDomains.slots_for(groups, [True, False], 0, 7)

    def test_single_process_mesh_partitions_by_hosts_arg(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1)
        assert dom.n_hosts == 1
        assert dom.alive_positions() == [0]
        assert dom.describe()["losses"] == 0

    def test_all_hosts_lost_is_unservable(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1)
        with pytest.raises(RuntimeError, match="all 1 hosts lost"):
            dom.mark_lost(0)

    def test_mark_lost_idempotent_and_counts(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1)
        dom.groups = FailureDomains.partition(4, 2)   # pretend 2 hosts
        dom.alive = [True, True]
        dom.mark_lost(1)
        dom.mark_lost(1)
        assert dom.n_losses == 1
        assert dom.alive_hosts() == [0]

    def test_poll_is_none_without_fault_plan(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1)
        assert dom.poll() is None

    def test_slow_escalates_to_lost_at_threshold(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1, slow_threshold=3)
        with faults.inject("mesh.host_slow(host=0, times=3, value=0.01)"):
            e1 = dom.poll()
            e2 = dom.poll()
            e3 = dom.poll()
        assert (e1.kind, e2.kind, e3.kind) == ("slow", "slow", "lost")
        assert e1.delay_s == pytest.approx(0.01)
        assert "escalated" in e3.cause

    def test_collective_timeout_names_presumed_host(self):
        mesh = make_mesh((1,), ("data",), jax.devices()[:1])
        dom = FailureDomains(mesh, hosts=1)
        dom.groups = FailureDomains.partition(4, 2)
        dom.alive = [True, True]
        with faults.inject("collective.timeout(value=0)"):
            ev = dom.poll()
        assert ev.kind == "lost" and ev.host == 0
        with faults.inject("collective.timeout"):
            ev = dom.poll()
        assert ev.host == 1   # default scapegoat: the last alive host


# ---------------------------------------------------------------------------
# mesh shrink: descriptors + elastic re-mesh
# ---------------------------------------------------------------------------

class TestShrink:
    def test_shrink_descriptor_halves_to_fit(self):
        assert ms.shrink_descriptor("data=8", 4) == "data=4"
        assert ms.shrink_descriptor("data=4", 2) == "data=2"
        assert ms.shrink_descriptor("data=8", 5) == "data=4"
        assert ms.shrink_descriptor("data=8", 8) == "data=8"
        assert ms.shrink_descriptor("single", 1) == "single"

    def test_shrink_descriptor_named_axis(self):
        assert ms.shrink_descriptor("data=4,model=2", 4,
                                    axis="data") == "data=2,model=2"
        with pytest.raises(ValueError, match="not in descriptor"):
            ms.shrink_descriptor("data=4", 2, axis="model")

    def test_shrink_descriptor_impossible(self):
        with pytest.raises(ValueError, match="not enough devices"):
            ms.shrink_descriptor("data=2,model=2", 1, axis="data")
        with pytest.raises(ValueError, match="n_devices"):
            ms.shrink_descriptor("data=2", 0)

    def test_elastic_remesh_descriptor_on_one_device(self):
        from repro.ft.resilience import elastic_remesh
        mesh = elastic_remesh("data=8")
        assert dict(mesh.shape) == {"data": 1}
        # legacy tuple form still accepted
        mesh = elastic_remesh((4, 1), ("data", "model"))
        assert dict(mesh.shape) == {"data": 1, "model": 1}
        with pytest.raises(TypeError):
            elastic_remesh("data=8", ("data",))   # descriptor + axis_names


# ---------------------------------------------------------------------------
# checksummed journal records (ft.artefacts)
# ---------------------------------------------------------------------------

class TestJournalRecords:
    def test_roundtrip_and_checksums(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        artefacts.append_record(p, {"kind": "submit", "rid": 0})
        artefacts.append_record(p, {"kind": "progress", "rid": 0,
                                    "tokens": [1, 2, 3]})
        recs, clean = artefacts.read_records(p)
        assert clean and len(recs) == 2
        assert recs[1]["tokens"] == [1, 2, 3]

    def test_missing_file_reads_empty_clean(self, tmp_path):
        recs, clean = artefacts.read_records(str(tmp_path / "nope.jsonl"))
        assert recs == [] and clean

    def test_torn_tail_recovers_to_last_complete_record(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        artefacts.append_record(p, {"kind": "submit", "rid": 0})
        artefacts.append_record(p, {"kind": "progress", "rid": 0,
                                    "tokens": [7]})
        with open(p, "a") as f:
            f.write('{"kind": "progress", "rid": 0, "tok')   # crash mid-write
        recs, clean = artefacts.read_records(p)
        assert not clean
        assert [r["kind"] for r in recs] == ["submit", "progress"]

    def test_flipped_bit_fails_checksum(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        artefacts.append_record(p, {"kind": "progress", "rid": 0,
                                    "tokens": [7]})
        artefacts.append_record(p, {"kind": "terminal", "rid": 0,
                                    "state": "ok"})
        lines = open(p).read().splitlines()
        lines[0] = lines[0].replace('"tokens":[7]', '"tokens":[8]')
        with open(p, "w") as f:
            f.write("\n".join(lines) + "\n")
        recs, clean = artefacts.read_records(p)
        # the tampered record AND everything after it are dropped: a
        # journal's order is part of its meaning
        assert recs == [] and not clean


class TestSchedulerJournal:
    def test_fold_to_state(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = SchedulerJournal(p)
        j.record_submit(0, [1, 2, 3], max_new=4, temperature=0.0, top_k=0,
                        stream=0)
        j.record_submit(1, [4, 5], max_new=2, temperature=0.9, top_k=5,
                        stream=1)
        j.record_progress(0, [10, 11])
        j.record_progress(0, [10, 11, 12])      # delta append
        j.record_progress(0, [10, 11, 12])      # no new tokens: no record
        j.record_terminal(1, "cancelled", "caller")
        j.record_terminal(1, "cancelled", "again")   # deduped
        state = SchedulerJournal.load(p)
        assert state.clean
        assert state.requests[0]["emitted"] == [10, 11, 12]
        assert state.requests[0]["prompt"] == [1, 2, 3]
        assert state.requests[1]["stream"] == 1
        assert state.terminals == {1: ("cancelled", "caller")}
        assert sorted(state.live()) == [0]

    def test_evacuate_resets_emitted_snapshot(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = SchedulerJournal(p)
        j.record_submit(0, [1], max_new=4, temperature=0.0, top_k=0,
                        stream=0)
        j.record_progress(0, [10, 11])
        j.record_evacuate(0, host=1)
        # after evacuation the request re-decodes from its prompt: the
        # journal writer's snapshot resets so the re-emitted tokens are
        # re-recorded from the first token
        j.record_progress(0, [10, 11, 12])
        state = SchedulerJournal.load(p)
        assert state.evacuations == 1
        assert state.requests[0]["emitted"] == [10, 11, 12]

    def test_shrink_records_collected(self, tmp_path):
        p = str(tmp_path / "j.jsonl")
        j = SchedulerJournal(p)
        j.record_shrink("data=8", "data=4", host=1, cause="drill")
        state = SchedulerJournal.load(p)
        assert len(state.shrinks) == 1
        assert state.shrinks[0]["frm"] == "data=8"
        assert state.shrinks[0]["to"] == "data=4"


# ---------------------------------------------------------------------------
# journal replay: token identity in a fresh engine
# ---------------------------------------------------------------------------

class TestReplay:
    @staticmethod
    def _reqs(cfg, n=3):
        """Decodes long enough (16 tokens, chunk=4) that nothing retires
        in the couple of boundaries before the simulated crash."""
        key = jax.random.PRNGKey(5)
        temps = [0.0, 0.9, 0.0]
        return [Request(
            prompt=jax.random.randint(jax.random.fold_in(key, 100 + i),
                                      (5 + 3 * i,), 0, cfg.vocab),
            max_new_tokens=16, temperature=temps[i % 3],
            top_k=(5 if i % 3 == 1 else 0)) for i in range(n)]

    def _abandon(self, model, params, reqs, jpath, key, *, chunks,
                 cancel_rid=None):
        """Drive a journaled engine partway and walk away (the crash)."""
        eng = ContinuousEngine(model, params, max_seq=64, slots=4, chunk=4,
                               journal=jpath)
        with eng._options_scope():
            eng._run_key = key
            for i, r in enumerate(reqs):
                eng.submit(r, stream=i)
            for _ in range(chunks):
                if eng.sched.idle:
                    break
                eng.step_chunk()
            if cancel_rid is not None:
                eng.cancel(cancel_rid, "raced with the crash")
        return eng

    def test_replay_matches_fault_free_oracle(self, dense_model, tmp_path):
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(7)
        reqs = self._reqs(cfg, 3)
        oracle = ContinuousEngine(model, params, max_seq=64, slots=4,
                                  chunk=4).run(reqs, key=key)
        jpath = str(tmp_path / "j.jsonl")
        self._abandon(model, params, reqs, jpath, key, chunks=2)
        fresh = ContinuousEngine(model, params, max_seq=64, slots=4, chunk=4)
        got = replay(jpath, fresh, key=key)
        assert sorted(got) == [0, 1, 2]
        for rid, toks in got.items():
            assert toks == oracle[rid], rid

    def test_replay_mid_prefill_submit_only(self, dense_model, tmp_path):
        """Crash before the first boundary: the journal holds bare submits
        (no progress); replay still owes — and reproduces — every token."""
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(9)
        reqs = self._reqs(cfg, 2)
        oracle = ContinuousEngine(model, params, max_seq=64, slots=4,
                                  chunk=4).run(reqs, key=key)
        jpath = str(tmp_path / "j.jsonl")
        self._abandon(model, params, reqs, jpath, key, chunks=0)
        state = SchedulerJournal.load(jpath)
        assert all(r["emitted"] == [] for r in state.requests.values())
        fresh = ContinuousEngine(model, params, max_seq=64, slots=4, chunk=4)
        got = replay(jpath, fresh, key=key)
        assert [got[i] for i in range(2)] == oracle

    def test_replay_skips_cancel_raced_request(self, dense_model, tmp_path):
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(11)
        reqs = self._reqs(cfg, 3)
        oracle = ContinuousEngine(model, params, max_seq=64, slots=4,
                                  chunk=4).run(reqs, key=key)
        jpath = str(tmp_path / "j.jsonl")
        self._abandon(model, params, reqs, jpath, key, chunks=1,
                      cancel_rid=1)
        state = SchedulerJournal.load(jpath)
        assert state.terminals[1][0] == "cancelled"
        fresh = ContinuousEngine(model, params, max_seq=64, slots=4, chunk=4)
        got = replay(jpath, fresh, key=key)
        # the cancelled request is terminal — replay owes it nothing
        assert sorted(got) == [0, 2]
        assert got[0] == oracle[0] and got[2] == oracle[2]

    def test_duplicate_replay_is_idempotent(self, dense_model, tmp_path):
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(13)
        reqs = self._reqs(cfg, 3)
        jpath = str(tmp_path / "j.jsonl")
        self._abandon(model, params, reqs, jpath, key, chunks=2)
        a = replay(jpath, ContinuousEngine(model, params, max_seq=64,
                                           slots=4, chunk=4), key=key)
        b = replay(jpath, ContinuousEngine(model, params, max_seq=64,
                                           slots=4, chunk=4), key=key)
        assert a == b

    def test_replay_survives_torn_tail(self, dense_model, tmp_path):
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(15)
        reqs = self._reqs(cfg, 2)
        oracle = ContinuousEngine(model, params, max_seq=64, slots=4,
                                  chunk=4).run(reqs, key=key)
        jpath = str(tmp_path / "j.jsonl")
        self._abandon(model, params, reqs, jpath, key, chunks=1)
        with open(jpath, "a") as f:
            f.write('{"kind": "termi')     # crash tore the last write
        state = SchedulerJournal.load(jpath)
        assert not state.clean
        got = replay(state, ContinuousEngine(model, params, max_seq=64,
                                             slots=4, chunk=4), key=key)
        assert [got[i] for i in range(2)] == oracle


# ---------------------------------------------------------------------------
# checkpoint manifests: checksummed, quarantined, fall back on restore
# ---------------------------------------------------------------------------

class TestCheckpointManifests:
    def _mgr(self, tmp_path):
        from repro.ckpt.manager import CheckpointManager
        return CheckpointManager(str(tmp_path / "ckpt"), keep=5,
                                 async_save=False)

    def test_corrupt_manifest_falls_back_to_older_step(self, tmp_path):
        mgr = self._mgr(tmp_path)
        state = {"w": np.arange(4, dtype=np.float32)}
        mgr.save(1, state, extra={"tokens": 10})
        mgr.save(2, {"w": np.arange(4, dtype=np.float32) * 2},
                 extra={"tokens": 20})
        manifest = os.path.join(mgr.dir, "step_0000000002", "manifest.json")
        faults.corrupt_json_file(manifest, "garbage")
        got = mgr.restore_latest(state)
        assert got is not None
        step, restored, extra = got
        assert step == 1 and extra == {"tokens": 10}
        np.testing.assert_array_equal(restored["w"], np.arange(4))
        # the corrupt manifest was quarantined, not deleted
        assert os.path.isdir(manifest + ".quarantine")
        # and its step no longer advertises itself
        assert mgr.all_steps() == [1]

    def test_stale_checksum_detected(self, tmp_path):
        """A manifest whose payload changed after checksumming (silent
        bitrot / manual edit) must not restore."""
        mgr = self._mgr(tmp_path)
        state = {"w": np.zeros(2, dtype=np.float32)}
        mgr.save(1, state)
        manifest = os.path.join(mgr.dir, "step_0000000001", "manifest.json")
        faults.corrupt_json_file(manifest, "stale")
        assert mgr.restore_latest(state) is None

    def test_clean_roundtrip(self, tmp_path):
        mgr = self._mgr(tmp_path)
        state = {"w": np.arange(6, dtype=np.float32)}
        mgr.save(3, state, extra={"step_time": 0.5})
        step, restored, extra = mgr.restore_latest(state)
        assert step == 3 and extra == {"step_time": 0.5}
        np.testing.assert_array_equal(restored["w"], state["w"])


# ---------------------------------------------------------------------------
# re-tuning for a shrunk mesh descriptor
# ---------------------------------------------------------------------------

class TestRetuneForMesh:
    def test_fills_cache_rows_for_descriptor(self, dense_model,
                                             tuning_cache):
        from repro import autotune
        cfg, _, _ = dense_model
        n = domains.retune_for_mesh(cfg, "data=2", max_seq=64,
                                    batch_sizes=(1, 8), cache=tuning_cache)
        assert n > 0
        # the descriptor is part of the cache key: a tune for the same
        # shrunk mesh now comes straight from cache
        shapes = list(autotune.model_kernel_shapes(cfg, max_seq=64,
                                                   batch_sizes=(1, 8)))
        hit = False
        for kernel, shape in shapes:
            try:
                r = autotune.tune(kernel, backend="shardmap", mesh="data=2",
                                  cache=tuning_cache, measure=False, **shape)
            except (ValueError, AssertionError):
                continue
            assert r.source == "cache", (kernel, r.source)
            hit = True
        assert hit


# ---------------------------------------------------------------------------
# forced-8-device host-loss drills (subprocesses; see conftest.forced_devices)
# ---------------------------------------------------------------------------

DRILL_COMMON = r"""
import os, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, ShardedEngine, Request
from repro.serve.domains import SchedulerJournal, replay
from repro.launch.mesh import make_mesh
from repro.testing import faults
from repro import obs

cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, max_seq=64)
model = Model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

def reqs():
    # decodes long enough (16 tokens, chunk=4) that every request is still
    # in flight when the fault fires a few boundaries in
    rng = np.random.RandomState(1)
    spec = [(3, 0.0, 0), (9, 0.8, 4), (5, 0.0, 0), (12, 1.2, 0),
            (4, 0.0, 0), (6, 0.0, 0), (7, 0.9, 3), (8, 0.0, 0)]
    return [Request(jnp.asarray(rng.randint(0, 128, (l,)), jnp.int32),
                    max_new_tokens=16, temperature=t, top_k=k)
            for l, t, k in spec]

key = jax.random.PRNGKey(7)
oracle = ContinuousEngine(model, params, max_seq=64, slots=8,
                          chunk=4).run(reqs(), key=key)

def mk_sharded(**kw):
    mesh = make_mesh((8,), ("data",))
    return ShardedEngine(model, params, max_seq=64, slots=8, chunk=4,
                         mesh=mesh, hosts=2, **kw)
"""


DRILL_HOST_LOSS = DRILL_COMMON + r"""
# -- clean run first: silent (zero dumps, zero degradations, zero losses) --
sh0 = mk_sharded()
assert sh0.run(reqs(), key=key) == oracle
assert obs.flight_dumps() == [], [d["reason"] for d in obs.flight_dumps()]
st = sh0.stats()
assert st["resilience"]["host_losses"] == 0, st["resilience"]
assert st["mesh"]["descriptor"] == "data=8", st["mesh"]
assert st["mesh"]["hosts"]["alive"] == [0, 1], st["mesh"]["hosts"]
assert sh0.sched.n_evacuations == 0
print("CLEAN_OK")

# -- elastic remesh on the real 8-device platform --------------------------
from repro.ft.resilience import elastic_remesh
assert dict(elastic_remesh("data=16").shape) == {"data": 8}
assert dict(elastic_remesh("data=8").shape) == {"data": 8}
print("REMESH_OK")

# -- host 1 dies mid-decode ------------------------------------------------
obs.flight_clear()
from repro.autotune import TuningCache
tmp = tempfile.mkdtemp()
jpath = os.path.join(tmp, "journal.jsonl")
tc = TuningCache(os.path.join(tmp, "tc.json"))
sh = mk_sharded(journal=jpath, tuning_cache=tc)
with faults.inject("mesh.host_lost(host=1, after=3)") as plan:
    got = sh.run(reqs(), key=key)
assert plan[0].fired == 1, plan[0].fired
# survivors retired token-identical; evacuees re-admitted on the shrunk
# mesh and completed bit-identical to the fault-free oracle
assert got == oracle, "tokens diverged from the fault-free oracle"
st = sh.stats()
assert st["mesh"]["descriptor"] == "data=4", st["mesh"]
assert st["mesh"]["hosts"]["alive"] == [0], st["mesh"]["hosts"]
assert st["mesh"]["hosts"]["lost"] == [1]
assert st["resilience"]["host_losses"] == 1
assert sh.sched.n_evacuations == 4, sh.sched.n_evacuations

# exactly ONE flight dump for the event, reason host_lost (the generic
# degradation dump is suppressed on this path)
dumps = obs.flight_dumps()
reasons = [d["reason"] for d in dumps]
assert reasons.count("host_lost") == 1, reasons
assert "degradation" not in reasons, reasons
assert dumps[[i for i, r in enumerate(reasons)
              if r == "host_lost"][0]]["ctx"]["to"] == "data=4"

# the shrink is a recorded strategy: provenance origin degraded(mesh(...))
assert "degraded(mesh(data=8)->mesh(data=4))" in obs.explain(), \
    obs.explain(kind="mesh")

# the autotuner re-ranked candidates for the shrunk descriptor
assert any("data=4" in k for k in tc._mem), sorted(tc._mem)[:5]

# the journal recorded the whole story, checksummed
state = SchedulerJournal.load(jpath)
assert state.clean
assert len(state.shrinks) == 1
assert state.shrinks[0]["frm"] == "data=8"
assert state.shrinks[0]["to"] == "data=4"
assert state.shrinks[0]["host"] == 1
assert state.evacuations == 4
assert sorted(state.terminals) == list(range(8))
assert all(s == "ok" for s, _ in state.terminals.values())
for rid in range(8):
    assert state.requests[rid]["emitted"] == oracle[rid], rid
print("LOSS_OK")
"""


DRILL_TIMEOUT_SLOW = DRILL_COMMON + r"""
# -- collective timeout: presumed-dead host (default: last alive) ----------
with faults.inject("collective.timeout(after=2)"):
    sh = mk_sharded()
    got = sh.run(reqs(), key=key)
assert got == oracle
st = sh.stats()
assert st["mesh"]["descriptor"] == "data=4", st["mesh"]
assert st["mesh"]["hosts"]["lost"] == [1], st["mesh"]["hosts"]
print("TIMEOUT_OK")

# -- straggler escalation: slow strikes, then lost (note host 0 this time:
# the shrunk mesh is the TAIL half, exercising the re-rank of positions) --
obs.flight_clear()
sh2 = mk_sharded(host_slow_threshold=2)
with faults.inject("mesh.host_slow(host=0, times=2, value=0.0)"):
    got = sh2.run(reqs(), key=key)
assert got == oracle
st = sh2.stats()
assert st["mesh"]["hosts"]["lost"] == [0], st["mesh"]["hosts"]
assert st["mesh"]["descriptor"] == "data=4"
reasons = [d["reason"] for d in obs.flight_dumps()]
assert reasons.count("host_lost") == 1, reasons
print("TIMEOUT_SLOW_OK")
"""


DRILL_REPLAY = DRILL_COMMON + r"""
# -- crash AFTER surviving a host loss: the journal replays the survivors
# and evacuees alike, in a fresh single-device engine, to token identity --
import tempfile, os
jpath = os.path.join(tempfile.mkdtemp(), "j.jsonl")
sh = mk_sharded(journal=jpath)
with faults.inject("mesh.host_lost(host=1, after=1)"):
    with sh._options_scope():
        sh._run_key = key
        for i, r in enumerate(reqs()):
            sh.submit(r, stream=i)
        for _ in range(3):
            sh.step_chunk()
# walk away mid-flight (the engine crash); a fresh unsharded engine owes
# every live request its tokens
state = SchedulerJournal.load(jpath)
assert state.clean
assert len(state.shrinks) == 1
assert len(state.live()) == 8, sorted(state.live())
cont = ContinuousEngine(model, params, max_seq=64, slots=8, chunk=4)
got = replay(jpath, cont, key=key)
assert sorted(got) == list(range(8))
for rid, toks in got.items():
    assert toks == oracle[rid], rid
print("REPLAY_DRILL_OK")
"""


@pytest.mark.slow
def test_host_loss_drill_subprocess(forced_devices):
    """Acceptance: on a forced-8-device mesh split into 2 hosts, killing
    host 1 mid-decode evacuates its slots, shrinks the mesh data=8->data=4,
    records the shrink as provenance ``degraded(mesh(...))`` + exactly one
    ``host_lost`` flight dump + a checksummed journal, re-tunes for the new
    descriptor — and every request retires token-identical to the
    fault-free oracle.  A clean run stays silent."""
    r = forced_devices(DRILL_HOST_LOSS)
    for marker in ("CLEAN_OK", "REMESH_OK", "LOSS_OK"):
        assert marker in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_timeout_and_straggler_drill_subprocess(forced_devices):
    """Collective timeouts and straggler escalation take the same survival
    path; losing host 0 (the leading half) exercises position re-ranking."""
    r = forced_devices(DRILL_TIMEOUT_SLOW)
    assert "TIMEOUT_SLOW_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_journal_replay_after_host_loss_subprocess(forced_devices):
    """A journal written through a host loss replays every live request to
    token identity in a fresh engine on a different (single-device)
    topology."""
    r = forced_devices(DRILL_REPLAY)
    assert "REPLAY_DRILL_OK" in r.stdout, r.stdout + r.stderr
