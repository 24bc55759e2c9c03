"""Pallas paged-attention + fused-sampling kernels (ISSUE 16).

Contract under test: (a) ``ops.paged_attention.paged_pool_attention``
matches the XLA gather reference (``paged_gather`` →
``paged_attention``) on fp32 and int8 pools, decode (C=1) and chunk
(C>1) shapes, sentinel page-table tails, and head-sharded tp pools via
``shard_map``; (b) ``ops.sampling.threshold_sample_logits`` is
BIT-identical to ``models.gpt.sample_logits`` — same key, same gumbel
draw, same kept set; (c) with ``BIGDL_TPU_PAGED_KERNEL=1`` the serving
stack is token-identical at temperature 0 across dense-prompt decode,
chunked prefill, speculative decode, int8 K/V and tp ∈ {1, 2, 4}, and
the ≤2-compile / O(1)-dispatch gates still hold; (d) shared
``ops.pallas_util.fit_block`` handles non-power-of-two sizes. All
kernel tests run the pallas interpret build of the identical kernel the
chip runs (``JAX_PLATFORMS=cpu``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.gpt import GPTForCausalLM, sample_logits
from bigdl_tpu.ops import sampling
from bigdl_tpu.ops.pallas_util import fit_block
from bigdl_tpu.ops.paged_attention import paged_pool_attention
from bigdl_tpu.ops.sampling import threshold_sample_logits
from bigdl_tpu.parallel.layout import serving_mesh
from bigdl_tpu.parallel.sequence import (paged_attention, paged_gather,
                                         paged_gather_dequant, paged_write,
                                         paged_write_quant)
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving.paging import PagedSlotManager
from bigdl_tpu.serving.slots import select_tokens

WAIT = 120.0

PROMPTS = [[5, 9, 2, 17, 3], [1, 1, 4, 60, 8], [7, 3, 3],
           [9, 9, 9, 1, 0, 2, 4]]


def _built(seed=0, **kw):
    cfg = dict(vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
               max_position=64)
    cfg.update(kw)
    m = GPTForCausalLM(**cfg)
    params, _ = m.setup(jax.random.PRNGKey(seed), None)
    return m, params


def _sequential(m, params, prompts, n_new):
    return [np.asarray(m.generate(params, jnp.asarray(p, jnp.int32)[None],
                                  n_new))[0]
            for p in prompts]


def _serve(engine, prompts, n_new):
    handles = [engine.submit(p, n_new) for p in prompts]
    return [engine.result(h, timeout=WAIT) for h in handles]


# ----------------------------------------------------- fit_block (shared) --
class TestFitBlock:
    def test_small_seq_returns_whole(self):
        assert fit_block(5, 8) == 5

    def test_divisor_at_want(self):
        assert fit_block(48, 8, align=8) == 8
        assert fit_block(48, 16, align=8) == 16

    def test_no_aligned_divisor_takes_the_whole_dimension(self):
        """A block that merely divides (6 of 12 heads, 2 of 10 rows) is
        refused by the TPU lowering; the whole dimension never is
        (tests/test_pallas_lowering.py lowers these cases for TPU)."""
        assert fit_block(12, 8, align=8) == 12   # GPT-2's heads
        assert fit_block(10, 4, align=8) == 10
        assert fit_block(7, 4, align=8) == 7
        assert fit_block(1000, 512) == 1000

    def test_prefers_128_multiples(self):
        assert fit_block(384, 256) == 128  # 256 ∤ 384; 128 | 384

    def test_odd_128_multiple(self):
        assert fit_block(640, 512) == 128  # 512, 384, 256 all ∤ 640


# ------------------------------------------------- kernel vs XLA reference --
def _build_pool(key, b, h, s_max, d, page_size, lengths, int8=False):
    """A pool + table as the allocator would leave them: per-row page
    runs in position order, ``num_pages`` sentinel tails, row with
    length 0 fully sentinel (the forced-inactive shape the step fns
    feed the kernel)."""
    npages_per_row = s_max // page_size
    n = sum(-(-max(length, 1) // page_size) for length in lengths) + 1
    kk, vk = jax.random.split(key)
    k = jax.random.normal(kk, (b, h, s_max, d), jnp.float32)
    v = jax.random.normal(vk, (b, h, s_max, d), jnp.float32)
    table = np.full((b, npages_per_row), n, np.int32)
    nxt = 0
    for i, length in enumerate(lengths):
        for j in range(-(-length // page_size)):
            table[i, j] = nxt
            nxt += 1
    pages = np.full((b, s_max), n, np.int32)      # sentinel -> write drops
    offs = np.zeros((b, s_max), np.int32)
    for i, length in enumerate(lengths):
        for t in range(length):
            pages[i, t] = table[i, t // page_size]
            offs[i, t] = t % page_size
    pages, offs = jnp.asarray(pages), jnp.asarray(offs)
    if int8:
        pool = {"k": jnp.zeros((n, h, page_size, d), jnp.int8),
                "v": jnp.zeros((n, h, page_size, d), jnp.int8),
                "k_scale": jnp.zeros((n, h, page_size), jnp.float32),
                "v_scale": jnp.zeros((n, h, page_size), jnp.float32)}
        pool["k"], pool["k_scale"] = paged_write_quant(
            pool["k"], pool["k_scale"], k, pages, offs)
        pool["v"], pool["v_scale"] = paged_write_quant(
            pool["v"], pool["v_scale"], v, pages, offs)
    else:
        pool = {"k": paged_write(jnp.zeros((n, h, page_size, d),
                                           jnp.float32), k, pages, offs),
                "v": paged_write(jnp.zeros((n, h, page_size, d),
                                           jnp.float32), v, pages, offs)}
    return pool, jnp.asarray(table)


def _reference(q, pool, table, q_pos):
    if "k_scale" in pool:
        kf = paged_gather_dequant(pool["k"], pool["k_scale"], table,
                                  jnp.float32)
        vf = paged_gather_dequant(pool["v"], pool["v_scale"], table,
                                  jnp.float32)
    else:
        kf = paged_gather(pool["k"], table)
        vf = paged_gather(pool["v"], table)
    return paged_attention(q, kf, vf, q_pos)


class TestKernelParity:
    B, H, D, PS, SMAX = 5, 4, 8, 8, 32
    LENGTHS = [5, 17, 32, 1, 0]       # partial / multi-page / full /
    #                                   single-token / forced-inactive

    def _q_pos(self, c):
        starts = [max(length - 1, 0) for length in self.LENGTHS]
        if c > 1:                     # chunk ending at the write frontier
            starts = [max(length - c, 0) for length in self.LENGTHS]
        return jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(c)

    @pytest.mark.parametrize("int8", [False, True], ids=["fp32", "int8"])
    @pytest.mark.parametrize("c", [1, 4], ids=["decode", "chunk"])
    def test_matches_xla_gather(self, int8, c):
        key = jax.random.PRNGKey(3)
        pool, table = _build_pool(key, self.B, self.H, self.SMAX, self.D,
                                  self.PS, self.LENGTHS, int8=int8)
        q = jax.random.normal(jax.random.PRNGKey(7),
                              (self.B, self.H, c, self.D), jnp.float32)
        q_pos = self._q_pos(c)
        got = paged_pool_attention(q, pool, table, q_pos)
        want = _reference(q, pool, table, q_pos)
        # the all-sentinel row is junk on BOTH paths — exclude it, like
        # the slot managers do
        active = np.asarray([length > 0 for length in self.LENGTHS])
        np.testing.assert_allclose(np.asarray(got)[active],
                                   np.asarray(want)[active],
                                   rtol=1e-5, atol=1e-5)
        assert np.isfinite(np.asarray(got)).all()   # junk is still finite

    def test_head_block_non_divisor_falls_back(self):
        pool, table = _build_pool(jax.random.PRNGKey(5), 2, 6, self.SMAX,
                                  self.D, self.PS, [9, 30])
        q = jax.random.normal(jax.random.PRNGKey(11), (2, 6, 1, self.D),
                              jnp.float32)
        q_pos = jnp.asarray([[8], [29]], jnp.int32)
        got = paged_pool_attention(q, pool, table, q_pos, head_block=4)
        want = _reference(q, pool, table, q_pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("c", [1, 4], ids=["decode", "chunk"])
    def test_twelve_heads_int8_match_xla_gather(self, c):
        lengths = [9, 30]
        pool, table = _build_pool(jax.random.PRNGKey(19), 2, 12, self.SMAX,
                                  self.D, self.PS, lengths, int8=True)
        q = jax.random.normal(jax.random.PRNGKey(23), (2, 12, c, self.D),
                              jnp.float32)
        q_pos = jnp.asarray([[n - c] for n in lengths],
                            jnp.int32) + jnp.arange(c)
        got = paged_pool_attention(q, pool, table, q_pos)
        want = _reference(q, pool, table, q_pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("tp", [1, 2, 4])
    def test_tp_shard_map_matches_single_device(self, multi_device_cpu,
                                                tp, monkeypatch):
        pool, table = _build_pool(jax.random.PRNGKey(13), 3, 4, self.SMAX,
                                  self.D, self.PS, [6, 20, 32])
        q = jax.random.normal(jax.random.PRNGKey(17), (3, 4, 1, self.D),
                              jnp.float32)
        q_pos = jnp.asarray([[5], [19], [31]], jnp.int32)
        want = paged_pool_attention(q, pool, table, q_pos)
        mesh = serving_mesh(tp)
        got = paged_pool_attention(q, pool, table, q_pos,
                                   mesh=(mesh, "tp"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


# --------------------------------------------- fused sampling bit-parity --
class TestFusedSampling:
    S, V = 8, 64

    @pytest.mark.parametrize("cfg", [
        (1.0, None, None), (0.7, None, None), (1.0, 5, None),
        (1.0, None, 0.9), (0.8, 10, 0.95),
    ], ids=["plain", "temp", "topk", "topp", "combined"])
    def test_bit_identical_to_xla_chain(self, cfg):
        temp, top_k, top_p = cfg
        for seed in (0, 1, 2):
            key = jax.random.PRNGKey(seed)
            logits = jax.random.normal(jax.random.PRNGKey(seed + 100),
                                       (self.S, self.V)) * 3.0
            want = sample_logits(logits, key, temp, top_k, top_p)
            got = threshold_sample_logits(logits, key, temp, top_k, top_p)
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got))

    def test_per_row_temperatures(self):
        key = jax.random.PRNGKey(4)
        logits = jax.random.normal(jax.random.PRNGKey(104),
                                   (self.S, self.V)) * 3.0
        temps = jnp.asarray([[0.5], [0.8], [1.0], [1.3], [0.7], [0.9],
                             [1.1], [0.6]], jnp.float32)
        want = sample_logits(logits, key, temps, 10, 0.9)
        got = threshold_sample_logits(logits, key, temps, 10, 0.9)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_non_divisible_row_count(self):
        # S=12: a whole block of 8 rows and one padded out from 4
        key = jax.random.PRNGKey(5)
        logits = jax.random.normal(jax.random.PRNGKey(105), (12, self.V))
        want = sample_logits(logits, key, 0.9, 7, 0.8)
        got = threshold_sample_logits(logits, key, 0.9, 7, 0.8)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# -------------------------------------- flag-on end-to-end token identity --
class TestPagedKernelFlagOn:
    """``BIGDL_TPU_PAGED_KERNEL=1``: the serving stack attends straight
    against the page pool; temperature-0 tokens must not change. The
    flag is read at model construction, so every test builds its model
    AFTER setenv (the sequential ``generate`` oracle never touches the
    paged path, so one model serves both sides)."""

    @pytest.fixture(autouse=True)
    def _flag(self, monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_PAGED_KERNEL", "1")

    def test_flag_is_read_at_construction(self):
        m, _ = _built()
        assert all(layer.attn.use_paged_kernel for layer in m.gpt.layers)

    def test_dense_prompt_paged_decode_token_identity(self):
        m, params = _built(seed=1)
        n_new = 8
        expected = _sequential(m, params, PROMPTS, n_new)
        pm = PagedSlotManager(m, params, max_slots=4, page_size=16)
        slots = pm.admit(PROMPTS)
        toks = []
        for _ in range(n_new):
            pm.reserve_block()
            toks.append(pm.step()[0])
        for exp, s, p in zip(expected, slots, PROMPTS):
            assert [int(t[s]) for t in toks] == exp[len(p):].tolist()

    def test_chunked_prefill_token_identity(self):
        m, params = _built(seed=2)
        n_new = 8
        expected = _sequential(m, params, PROMPTS, n_new)
        engine = ServingEngine(m, params, max_slots=4, max_queue=16,
                               paged=True, page_size=8, prefill_chunk=4)
        try:
            for exp, got in zip(expected, _serve(engine, PROMPTS, n_new)):
                np.testing.assert_array_equal(exp, got)
        finally:
            engine.shutdown()

    def test_speculative_decode_token_identity(self):
        m, params = _built(seed=3)
        n_new = 8
        expected = _sequential(m, params, PROMPTS, n_new)
        engine = ServingEngine(m, params, max_slots=4, max_queue=16,
                               paged=True, page_size=8, spec_tokens=3)
        try:
            for exp, got in zip(expected, _serve(engine, PROMPTS, n_new)):
                np.testing.assert_array_equal(exp, got)
        finally:
            engine.shutdown()

    def test_int8_kv_token_identity_vs_flag_off(self, monkeypatch):
        """int8 quantization can legitimately move tokens vs f32, so
        the oracle here is the flag-OFF int8 engine: in-kernel dequant
        must match gather-then-dequant token for token."""
        n_new = 8
        m_on, params = _built(seed=4)
        pm = PagedSlotManager(m_on, params, max_slots=4, page_size=16,
                              int8_kv=True)
        monkeypatch.delenv("BIGDL_TPU_PAGED_KERNEL")
        m_off, params_off = _built(seed=4)
        pm_off = PagedSlotManager(m_off, params_off, max_slots=4,
                                  page_size=16, int8_kv=True)
        assert not any(layer.attn.use_paged_kernel
                       for layer in m_off.gpt.layers)
        outs = []
        for mgr in (pm, pm_off):
            slots = mgr.admit(PROMPTS)
            toks = []
            for _ in range(n_new):
                mgr.reserve_block()
                toks.append(mgr.step()[0])
            outs.append([[int(t[s]) for t in toks] for s in slots])
        assert outs[0] == outs[1]

    @pytest.mark.parametrize("tp", [1, 2, 4])
    def test_tp_token_identity(self, multi_device_cpu, tp):
        m, params = _built(seed=5)
        n_new = 8
        expected = _sequential(m, params, PROMPTS, n_new)
        engine = ServingEngine(m, params, max_slots=4, max_queue=16,
                               paged=True, page_size=8, tp=tp)
        try:
            for exp, got in zip(expected, _serve(engine, PROMPTS, n_new)):
                np.testing.assert_array_equal(exp, got)
        finally:
            engine.shutdown()

    def test_compiles_once_and_dispatches_o1(self):
        """The kernel path must not cost extra traces or dispatches:
        same gates as the XLA path (tests/test_paging.py)."""
        m, params = _built(seed=6)
        n_new = 8
        chunk = 4
        engine = ServingEngine(m, params, max_slots=3, max_queue=16,
                               paged=True, prefill_window=2,
                               prefill_chunk=chunk)
        try:
            for h in [engine.submit(p, n_new) for p in PROMPTS]:
                engine.result(h, timeout=WAIT)
            st = dict(engine.stats)
            generated = engine.scheduler.generated_tokens
        finally:
            engine.shutdown()
        assert st["step_traces"] <= 2
        assert st["prefill_traces"] <= 2
        max_chunks = sum(-(-len(p) // chunk) for p in PROMPTS)
        assert st["dispatches"] <= max_chunks + generated + len(PROMPTS)
        assert generated == len(PROMPTS) * n_new


class TestFusedSamplingFlagOn:
    """The sampled branch through ``ops/sampling.py``: sampled tokens are
    bit-identical to the XLA chain (same key, same gumbel). No CPU table
    selects the kernel, so each test overrides the table's word
    (``sampling.applies``) or hands ``select_tokens`` its argument; each
    side builds fresh jitted closures."""

    def test_generate_bit_identical(self, monkeypatch):
        ids = jnp.asarray([PROMPTS[0]], jnp.int32)
        outs = {}
        for kernel in (False, True):
            monkeypatch.setattr(sampling, "applies", lambda *a: kernel)
            m, params = _built(seed=7)      # fresh _gen_fns per side
            outs[kernel] = np.asarray(m.generate(
                params, ids, 6, temperature=0.8, top_k=20, top_p=0.9,
                rng=jax.random.PRNGKey(42)))
            assert m.decode_stats["decode_traces"] == 1
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_serving_select_tokens_bit_identical(self, monkeypatch):
        outs = {}
        for sampler in ("sort", "kernel"):
            monkeypatch.setattr(sampling, "applies",
                                lambda *a: sampler == "kernel")
            m, params = _built(seed=8)
            pm = PagedSlotManager(m, params, max_slots=2, page_size=16,
                                  top_k=10, top_p=0.9, seed=7)
            assert pm.sampler == sampler
            slots = pm.admit(PROMPTS[:2], temperatures=[0.7, 0.9])
            assert pm.sampled() == 2
            toks = []
            for _ in range(4):
                pm.reserve_block()
                toks.append(pm.step()[0])
            outs[sampler] = [[int(t[s]) for t in toks] for s in slots]
        assert outs["sort"] == outs["kernel"]
        # and by ``select_tokens``' own argument, one greedy row of three
        logits = jax.random.normal(jax.random.PRNGKey(3), (3, 97)) * 3.0
        temps = jnp.asarray([0.7, 0.0, 1.1])
        want, _ = select_tokens(logits, temps, jax.random.key(5), 10, 0.9,
                                "sort")
        got, _ = select_tokens(logits, temps, jax.random.key(5), 10, 0.9,
                               "kernel")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_both_kernels_compose(self, monkeypatch):
        """Paged kernel + sampling kernel together, temp-0 rows greedy:
        token-identical to the all-XLA engine."""
        monkeypatch.setenv("BIGDL_TPU_PAGED_KERNEL", "1")
        monkeypatch.setattr(sampling, "applies", lambda *a: True)
        m, params = _built(seed=9)
        n_new = 6
        expected = _sequential(m, params, PROMPTS[:3], n_new)
        engine = ServingEngine(m, params, max_slots=4, max_queue=16,
                               paged=True, page_size=8)
        try:
            assert engine.slots.sampler == "kernel"
            for exp, got in zip(expected,
                                _serve(engine, PROMPTS[:3], n_new)):
                np.testing.assert_array_equal(exp, got)
        finally:
            engine.shutdown()
