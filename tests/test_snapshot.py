"""Crash-consistent serving recovery (ISSUE 13).

The contract under test (acceptance): with KV snapshots enabled a
rebuilt engine restores shared prompt state from the page store —
temperature-0 token-identical to the uninterrupted run — and falls back
per-stream to re-prefill on any digest miss, checksum failure, or
injected snapshot fault, never double-delivering a token; the journal
and store stay bounded; and restore-based recovery on the long-prompt,
many-stream scenario is at least 3x faster than forced re-prefill.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.gpt import GPTForCausalLM
from bigdl_tpu.resilience import faults, preempt
from bigdl_tpu.resilience.supervisor import EngineSupervisor
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving.snapshot import (KVSnapshot, PageStore,
                                        RequestJournal, chain_digests)

WAIT = 120.0


@pytest.fixture(autouse=True)
def _clean_harness():
    faults.configure(None)
    preempt.clear()
    yield
    faults.configure(None)
    preempt.clear()


def _built(seed=0, **kw):
    cfg = dict(vocab_size=61, hidden_size=32, n_layers=2, n_heads=4,
               max_position=64)
    cfg.update(kw)
    m = GPTForCausalLM(**cfg)
    params, _ = m.setup(jax.random.PRNGKey(seed), None)
    return m, params


def _sequential(m, params, prompts, n_new):
    return [np.asarray(m.generate(params, jnp.asarray(p, jnp.int32)[None],
                                  n_new))[0]
            for p in prompts]


def _planes(seed, layers=2, heads=4, page=4, dim=8):
    rng = np.random.default_rng(seed)
    return [{"k": rng.standard_normal((heads, page, dim)).astype("float32"),
             "v": rng.standard_normal((heads, page, dim)).astype("float32")}
            for _ in range(layers)]


def _digest(i):
    return bytes([i]) * 16


# ------------------------------------------------------------ page store --
class TestPageStore:
    def test_roundtrip(self, tmp_path):
        store = PageStore(tmp_path)
        items = [(_digest(i), _planes(i)) for i in range(3)]
        assert store.put_batch(items) == 3
        assert len(store) == 3
        for dig, planes in items:
            assert store.has(dig)
            got = store.get(dig)
            for a, b in zip(got, planes):
                for k in b:
                    np.testing.assert_array_equal(a[k], b[k])
        assert store.pages_written == 3
        assert store.pages_restored == 3
        # a fresh store over the same directory sees the same pages
        again = PageStore(tmp_path)
        assert again.digests() == {d for d, _ in items}

    def test_on_disk_corruption_demoted(self, tmp_path):
        store = PageStore(tmp_path)
        store.put_batch([(_digest(1), _planes(1))])
        (page_file,) = list((tmp_path / "pages").glob("*.page"))
        page_file.write_bytes(b"\x00" * 64)       # torn write survived
        assert store.get(_digest(1)) is None
        assert store.corrupt_dropped == 1
        assert not store.has(_digest(1))          # demoted, not retried
        assert not page_file.exists()

    def test_injected_write_corruption_demoted_on_read(self, tmp_path):
        faults.configure("serving.snapshot_write:corrupt=garbage:times=1")
        store = PageStore(tmp_path)
        store.put_batch([(_digest(1), _planes(1))])
        assert store.has(_digest(1))              # rename won the race...
        assert store.get(_digest(1)) is None      # ...checksum catches it
        assert store.corrupt_dropped == 1

    def test_injected_write_error_skips_page(self, tmp_path):
        faults.configure("serving.snapshot_write:error:times=1")
        store = PageStore(tmp_path)
        assert store.put_batch([(_digest(1), _planes(1)),
                                (_digest(2), _planes(2))]) == 1
        assert store.write_errors == 1
        assert not store.has(_digest(1)) and store.has(_digest(2))

    def test_injected_restore_fault_is_a_miss(self, tmp_path):
        store = PageStore(tmp_path)
        store.put_batch([(_digest(1), _planes(1))])
        faults.configure("serving.snapshot_restore:error:times=1")
        assert store.get(_digest(1)) is None      # fault -> miss
        assert store.get(_digest(1)) is not None  # page itself is fine
        assert store.restore_misses == 1 and store.corrupt_dropped == 0

    def test_gc_respects_pins_and_recency(self, tmp_path):
        store = PageStore(tmp_path)
        store.put_batch([(_digest(i), _planes(i)) for i in range(6)])
        store.pin(7, [_digest(0)])                # oldest, but pinned
        assert store.gc(3) == 3
        assert len(store) == 3
        assert store.has(_digest(0))              # pin exempted it
        assert store.has(_digest(4)) and store.has(_digest(5))
        store.release(7)
        assert store.pinned_streams() == 0
        assert store.gc(1) == 2

    def test_torn_manifest_starts_empty(self, tmp_path):
        store = PageStore(tmp_path)
        store.put_batch([(_digest(1), _planes(1))])
        (tmp_path / "MANIFEST.json").write_text("{ torn")
        again = PageStore(tmp_path)
        assert len(again) == 0                    # orphaned, not crashed
        assert again.get(_digest(1)) is None


# --------------------------------------------------------------- journal --
class TestRequestJournal:
    def test_admit_deliver_retire_replay(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = RequestJournal(path)
        j.admit(1, [5, 9, 2], 8, temperature=0.0, eos_token=60)
        j.admit(2, [7, 3], 4)
        j.delivered(1, 0, [10, 11])
        j.delivered(1, 2, [12])
        j.retire(2)
        j.close()
        live = RequestJournal.replay(path)
        assert set(live) == {1}
        assert live[1]["prompt"] == [5, 9, 2]
        assert live[1]["tokens"] == [10, 11, 12]
        assert live[1]["eos"] == 60 and live[1]["max_new_tokens"] == 8

    def test_replay_never_double_delivers(self, tmp_path):
        """A journal whose tail duplicates / overlaps chunks (crash
        between delivery and append, replayed twice) applies every
        token exactly once."""
        path = str(tmp_path / "journal.jsonl")
        recs = [{"op": "admit", "rid": 1, "prompt": [1], "max_new_tokens": 9,
                 "temperature": 0.0, "eos": None},
                {"op": "tok", "rid": 1, "off": 0, "toks": [10, 11]},
                {"op": "tok", "rid": 1, "off": 0, "toks": [10, 11]},   # dup
                {"op": "tok", "rid": 1, "off": 1, "toks": [11, 12]},   # lap
                {"op": "tok", "rid": 1, "off": 9, "toks": [99]}]       # gap
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
            f.write('{"op":"tok","rid":1,"off":3,"to')  # torn final line
        live = RequestJournal.replay(path)
        assert live[1]["tokens"] == [10, 11, 12]

    def test_idempotent_admit(self, tmp_path):
        j = RequestJournal(str(tmp_path / "j.jsonl"))
        j.admit(1, [1, 2], 4)
        j.delivered(1, 0, [9])
        j.admit(1, [1, 2], 4)       # recovery re-placement re-admits
        assert j.live()[1]["tokens"] == [9]
        j.close()

    def test_compaction_bounds_growth(self, tmp_path):
        j = RequestJournal(str(tmp_path / "j.jsonl"), compact_min=16)
        for rid in range(300):
            j.admit(rid, [1, 2, 3], 4)
            for off in range(4):
                j.delivered(rid, off, [off])
            j.retire(rid)
            assert j.record_count() <= 64        # never runaway
        assert j.compactions > 0
        assert not j.live()
        j.close()
        assert len(RequestJournal.replay(str(tmp_path / "j.jsonl"))) == 0

    def test_reopen_recovers_and_compacts(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = RequestJournal(path)
        j.admit(1, [1], 4)
        j.delivered(1, 0, [7, 8])
        j.admit(2, [2], 4)
        j.retire(2)
        j.close()
        j2 = RequestJournal(path)
        assert set(j2.live()) == {1}
        assert j2.live()[1]["tokens"] == [7, 8]
        assert j2.record_count() == 2            # started compacted
        j2.close()


# ---------------------------------------------------------- digest match --
class TestChainDigests:
    def test_matches_engine_prefix_registry(self):
        """The store's restore keys are the SAME digests the paged
        admission walk computes — a snapshot from one engine is
        addressable from any other."""
        m, params = _built(0)
        eng = ServingEngine(m, params, max_slots=2, paged=True,
                            kv_pages=16, page_size=4, prefill_chunk=4)
        try:
            prompt = [5, 9, 2, 17, 3, 1, 4, 8, 11]      # 2 full pages
            eng.generate(prompt, 2, timeout=WAIT)
            registered = {d for d, _ in eng.slots.allocator.registered()}
            digs = chain_digests(prompt, 4)
            assert len(digs) == 2
            assert set(digs) <= registered
        finally:
            eng.shutdown(drain=False)


# ---------------------------------------------------------- restore path --
def _snap_engine(m, params, d, **kw):
    ekw = dict(max_slots=8, paged=True, kv_pages=32, page_size=4,
               prefill_chunk=4, kv_snapshot=True, snapshot_dir=str(d),
               snapshot_interval_s=0.0)
    ekw.update(kw)
    return ServingEngine(m, params, **ekw)


PROMPTS8 = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3],
            [9, 9, 9, 1, 0, 2, 4], [2, 4], [11, 12, 13, 14, 15, 16],
            [6, 6, 6, 6, 6, 7, 8, 9], [3, 1, 4, 1, 5, 9, 2, 6, 5]]


class TestRestore:
    def test_flag_default_off(self):
        m, params = _built(0)
        eng = ServingEngine(m, params, max_slots=2, paged=True, kv_pages=8)
        try:
            assert eng.snapshot is None
            assert eng.slots.page_store is None
        finally:
            eng.shutdown(drain=False)

    def test_requires_paged_and_dir(self, tmp_path):
        m, params = _built(0)
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(m, params, kv_snapshot=True,
                          snapshot_dir=str(tmp_path))
        with pytest.raises(ValueError, match="directory"):
            ServingEngine(m, params, paged=True, kv_pages=8,
                          kv_snapshot=True)

    def test_restart_restores_token_identical(self, tmp_path):
        """Engine 2 over engine 1's snapshot directory serves the same
        prompts from restored pages — no recompute, same tokens."""
        m, params = _built(0)
        oracle = _sequential(m, params, PROMPTS8, 8)
        eng = _snap_engine(m, params, tmp_path)
        try:
            for h, want in zip([eng.submit(p, 8) for p in PROMPTS8],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
        finally:
            assert eng.shutdown(drain=True)
        assert eng.snapshot.store.pages_written > 0
        assert not eng.snapshot.journal.live()     # all retired out

        eng2 = _snap_engine(m, params, tmp_path)
        try:
            for h, want in zip([eng2.submit(p, 8) for p in PROMPTS8],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
            assert eng2.slots.restored_pages > 0
            mets = eng2.metrics()
            assert mets["snapshot_pages_restored"] > 0
        finally:
            eng2.shutdown(drain=False)

    def test_corrupt_store_falls_back_to_reprefill(self, tmp_path):
        """Every snapshot page mangled on disk: restore demotes them all
        and admission degrades to plain re-prefill — same tokens, no
        junk K/V."""
        m, params = _built(0)
        oracle = _sequential(m, params, PROMPTS8[:4], 8)
        eng = _snap_engine(m, params, tmp_path)
        try:
            for p in PROMPTS8[:4]:
                eng.generate(p, 8, timeout=WAIT)
        finally:
            eng.shutdown(drain=True)
        for f in (tmp_path / "pages").glob("*.page"):
            f.write_bytes(b"junk")
        eng2 = _snap_engine(m, params, tmp_path)
        try:
            for h, want in zip([eng2.submit(p, 8) for p in PROMPTS8[:4]],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
            assert eng2.slots.restored_pages == 0
            assert eng2.snapshot.store.corrupt_dropped > 0
        finally:
            eng2.shutdown(drain=False)

    @pytest.mark.parametrize("tp_write,tp_read", [(2, 1), (1, 2)])
    def test_restore_across_tp_degrees(self, tmp_path, multi_device_cpu,
                                       tp_write, tp_read):
        """ISSUE 15: snapshots are mesh-portable. Export gathers each
        page to a fully-replicated host copy (full head axis), so pages
        written by a tp=2 engine restore on a tp=1 engine and vice
        versa — token-identical, with real page reuse."""
        m, params = _built(0)
        oracle = _sequential(m, params, PROMPTS8[:4], 8)
        eng = _snap_engine(m, params, tmp_path, tp=tp_write)
        try:
            for h, want in zip([eng.submit(p, 8) for p in PROMPTS8[:4]],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
        finally:
            assert eng.shutdown(drain=True)
        assert eng.snapshot.store.pages_written > 0

        eng2 = _snap_engine(m, params, tmp_path, tp=tp_read)
        try:
            for h, want in zip([eng2.submit(p, 8) for p in PROMPTS8[:4]],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
            assert eng2.slots.restored_pages > 0
        finally:
            eng2.shutdown(drain=False)


# ------------------------------------------------------------ supervisor --
def _supervised_snap(m, params, d, engine_kw=None, **kw):
    ekw = dict(max_slots=8, max_recoveries=0, paged=True, kv_pages=32,
               page_size=4, prefill_chunk=4, kv_snapshot=True,
               snapshot_dir=str(d), snapshot_interval_s=0.0)
    ekw.update(engine_kw or {})

    def factory():
        return ServingEngine(m, params, **ekw)

    kw.setdefault("poll_interval_s", 0.02)
    kw.setdefault("backoff_base_s", 0.01)
    kw.setdefault("backoff_max_s", 0.05)
    return EngineSupervisor(factory, **kw)


class TestSupervisorRestore:
    def test_crash_mid_decode_restores_token_identical(self, tmp_path):
        """The acceptance leg: an engine killed mid-decode under 8
        concurrent paged streams; the supervisor rebuild re-attaches
        every stream and completes temperature-0 token-identical, with
        restored pages doing the work the re-prefill path used to."""
        m, params = _built(0)
        oracle = _sequential(m, params, PROMPTS8, 10)
        sup = _supervised_snap(m, params, tmp_path)
        try:
            # warm pass: compiles + populates the store via retirement
            for h, want in zip([sup.submit(p, 10) for p in PROMPTS8],
                               oracle):
                np.testing.assert_array_equal(h.result(WAIT), want)
            assert sup.engine.snapshot.store.pages_written > 0
            faults.configure("serving.step:error:after=3:times=1")
            handles = [sup.submit(p, 10) for p in PROMPTS8]
            outs = [h.result(WAIT) for h in handles]
            for got, want in zip(outs, oracle):
                np.testing.assert_array_equal(got, want)
            assert sup.restarts == 1
            assert sup.last_recovery_s is not None
            # the rebuilt engine restored pages instead of recomputing
            assert sup.engine.slots.restored_pages > 0
        finally:
            sup.close(drain=False)

    def test_wedge_grace_extends_during_restore(self, tmp_path):
        """A slow restore inside the wedge window is busy-but-healthy:
        with restore_grace_s the supervisor waits it out..."""
        m, params = _built(0)
        sup = _supervised_snap(m, params, tmp_path,
                               wedge_timeout_s=0.15, warmup_grace_s=20.0)
        try:
            sup.generate(PROMPTS8[0], 2, timeout=WAIT)    # compile warmup
            faults.configure(
                "serving.snapshot_restore:delay=1.0:times=1")
            out = sup.generate(PROMPTS8[7], 2, timeout=WAIT)
            assert out is not None
            assert sup.restarts == 0
        finally:
            sup.close(drain=False)

    def test_wedge_without_restore_grace_restarts(self, tmp_path):
        """...and with restore_grace_s=0 the same delay IS a wedge —
        proving the grace extension is what saves the restoring
        engine (the test has teeth). warmup_grace_s shields cold
        compile only (it applies while generated_tokens == 0), so it
        cannot mask the mid-serve restore delay this test injects."""
        m, params = _built(0)
        sup = _supervised_snap(m, params, tmp_path,
                               wedge_timeout_s=0.15, warmup_grace_s=20.0,
                               restore_grace_s=0.0)
        try:
            sup.generate(PROMPTS8[0], 2, timeout=WAIT)
            faults.configure(
                "serving.snapshot_restore:delay=1.5:times=1")
            sup.generate(PROMPTS8[7], 2, timeout=WAIT)
            assert sup.restarts >= 1
        finally:
            sup.close(drain=False)


# -------------------------------------------------------- bounded growth --
class TestBoundedGrowth:
    def test_journal_and_store_stay_bounded(self, tmp_path):
        """Hygiene satellite: rounds of admissions (including truncated
        force-retirements) leave zero live journal entries, a bounded
        record count, a gc-capped store, and no leaked pins."""
        m, params = _built(0)
        eng = _snap_engine(m, params, tmp_path, max_slots=4, kv_pages=24)
        eng.snapshot.max_pages = 16
        eng.snapshot.journal.compact_min = 16
        try:
            for i in range(6):
                prompts = [[(i * 7 + j * 3 + k) % 61 for k in range(5 + j)]
                           for j in range(4)]
                handles = [eng.submit(p, 6) for p in prompts]
                for h in handles:
                    h.result(WAIT)
            # a truncated force-retire must also compact out
            long_new = eng.slots.max_position        # exceeds capacity
            h = eng.submit([1] * 40, 23)
            h.result(WAIT)
            del long_new
            assert eng.snapshot.flush()
            j = eng.snapshot.journal
            assert not j.live()
            assert j.record_count() <= 2 * j.compact_min
            assert eng.snapshot.store.pinned_streams() == 0
        finally:
            eng.shutdown(drain=True)
        assert len(eng.snapshot.store) <= 16


# ------------------------------------------------------------ chaos soak --
class TestSnapshotChaos:
    @pytest.mark.slow
    def test_chaos_soak_snapshot_randomized(self, tmp_path):
        """Randomized crash-point soak (seed printed for replay):
        snapshot-write corruption, mid-restore faults, and step crashes
        all at once. Every request that completes must be token-
        identical to the oracle (which also proves no double delivery);
        nothing may hang."""
        seed = int(os.environ.get("BIGDL_TPU_CHAOS_SEED", "") or
                   int.from_bytes(os.urandom(2), "big"))
        print(f"snapshot chaos soak seed={seed} "
              f"(replay: BIGDL_TPU_CHAOS_SEED={seed} scripts/chaos.sh)")
        m, params = _built(0)
        oracle = {tuple(p): np.asarray(w) for p, w in
                  zip(PROMPTS8, _sequential(m, params, PROMPTS8, 8))}
        sup = _supervised_snap(m, params, tmp_path, max_restarts=50)
        try:
            sup.generate(PROMPTS8[0], 2, timeout=WAIT)
            faults.configure(
                f"seed={seed};"
                "serving.snapshot_write:corrupt:p=0.2;"
                "serving.snapshot_write:error:p=0.1;"
                "serving.snapshot_restore:error:p=0.2;"
                "serving.step:error:p=0.04")
            for _ in range(4):
                handles = [sup.submit(p, 8) for p in PROMPTS8]
                for p, h in zip(PROMPTS8, handles):
                    try:
                        got = h.result(WAIT)
                    except TimeoutError:
                        pytest.fail(f"hung request (seed={seed})")
                    except Exception:     # noqa: BLE001 — clean failure
                        continue
                    np.testing.assert_array_equal(
                        got, oracle[tuple(p)],
                        err_msg=f"token drift (seed={seed})")
        finally:
            sup.close(drain=False)


# ------------------------------------------------------- recovery speed --
class TestRecoverySpeed:
    def test_restore_beats_reprefill_3x(self, tmp_path):
        """The long-prompt, many-stream scenario: a warm store turns
        recovery into O(restore). Asserted on the WORK, by the manager's
        own counters: the restoring pass computes no prompt token (every
        page comes from the store, each prompt replays one logits-only
        chunk) where the pass against an empty store computes every one.
        The wall-clock ratio (3x and more on an idle CPU) is printed, not
        asserted: tier-1 shares the CPU among six workers."""
        m, params = _built(0, hidden_size=128, n_layers=4,
                           max_position=256)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 61, size=192).tolist()
                   for _ in range(8)]
        warm = rng.integers(0, 61, size=192).tolist()
        n_tokens = 8 * 192

        def run(d, measure_prompts):
            eng = ServingEngine(m, params, max_slots=8, paged=True,
                                kv_pages=160, page_size=16,
                                prefill_chunk=32, kv_snapshot=True,
                                snapshot_dir=str(d),
                                snapshot_interval_s=0.0)
            try:
                eng.generate(warm, 2, timeout=WAIT)   # compile warmup
                slots = eng.slots
                before = (slots.prefix_miss_tokens, slots.prefix_hit_tokens)
                t0 = time.perf_counter()
                handles = [eng.submit(p, 2) for p in measure_prompts]
                for h in handles:
                    h.result(WAIT)
                dt = time.perf_counter() - t0
                computed = slots.prefix_miss_tokens - before[0]
                reused = slots.prefix_hit_tokens - before[1]
                restored = slots.restored_pages
            finally:
                eng.shutdown(drain=True)
            return dt, restored, computed, reused

        # pass 1 populates the store (timing discarded)
        run(tmp_path, prompts)
        # pass 2 restores everything pass 1 persisted
        t_restore, restored, computed, reused = run(tmp_path, prompts)
        assert restored >= 8 * (192 // 16)        # full coverage
        assert (computed, reused) == (0, n_tokens)
        # forced re-prefill: same work against an EMPTY store
        cold = tmp_path / "cold"
        t_reprefill, r2, computed, reused = run(cold, prompts)
        assert r2 == 0
        assert (computed, reused) == (n_tokens, 0)
        print(f"recovery_speedup: {t_reprefill / t_restore:.2f}x "
              f"(restore {t_restore:.3f}s vs re-prefill "
              f"{t_reprefill:.3f}s)")
