"""The EvaByte configuration's own arithmetic and readers, on the CPU: the counts against numbers worked out by hand and against the
program's tree, the configuration file against the catalog's published keys it repeats, the three per-layer readers on hand-made spans
and a hand-made trace, the reference's independence of the program, and the cell's rehearsal."""
import json
import os

import pytest

from benchmarks.harness import counts_evabyte as counts
from benchmarks.harness import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "evabyte-longdoc-generate"
CONFIG = "evabyte-6.5b-serve"
NEW = ["mfu.serve.evabyte", "decode_step_roofline.evabyte", "eva_rows_needed_mean"]
# not `tokens_per_s`, nor the two metrics that move it: a closed loop of 20 streams admits 29 to 39 requests a window by the seed and
# each admission stalls every stream for its prefill, so the rate spreads 4.5 % over six seeds against half a bound of 2 % (PERF.md)
JOINED = ["tpot_p95_ms", "slot_occupancy_mean", "decode_step_ms", "device_idle_share.serve", "window_compiles", "step_host_ms",
          "idle_named_share.serve"]


def _run_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return _run_module()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts --
def test_param_count_is_the_issues_arithmetic(config):
    s = counts.shape(config)
    assert counts.layer_matrix_params(s) == 4 * 4096 * 4096 + 3 * 4096 * 11008                  # 67.1 M + 135.3 M
    assert counts.head_params(s) == 4096 * 8 * 320                                              # 10.5 M
    # a layer: its matrices, two norm gains, phi and mu: 202.4 M; 8 of them, the embedding, the final norm and the head
    assert counts.param_count(s) == 8 * (202_375_168 + 4 * 4096) + 320 * 4096 + 4096 + 10_485_760
    assert round(counts.param_count(s) / 1e6) == 1631 and round(2 * counts.param_count(s) / 1e9, 2) == 3.26


def test_param_count_is_the_programs_tree(config):
    import jax
    from benchmarks.harness import resolve
    model = resolve(config["constructor"])(**config["constructor_kwargs"])
    tree = jax.eval_shape(lambda k: model.setup(k, None)[0], jax.random.key(0))
    assert sum(int(l.size) for l in jax.tree_util.tree_leaves(tree)) == counts.param_count(counts.shape(config))
    cache = jax.eval_shape(lambda: model.init_cache(config["engine_kwargs"]["max_slots"], "bfloat16"))
    held = sum(int(l.size) * 2 for l in jax.tree_util.tree_leaves(cache))
    assert held == 20 * 8 * (2048 + 1024) * counts.row_bytes(counts.shape(config), 2) == 8_053_063_680        # 8.05 GB of tables


def test_the_file_repeats_the_published_keys_and_changes_the_depth_alone(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(entry["reduced"]) == {"num_hidden_layers"}
    assert (config["published"]["num_hidden_layers"], config["num_hidden_layers"]) == (32, 8)
    kw = config["constructor_kwargs"]
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "window_size", "chunk_size",
                "num_pred_heads", "rms_norm_eps", "rope_theta"):
        assert kw[key] == config[key], key
    assert config["num_key_value_heads"] == config["num_attention_heads"] and kw["max_position"] == 16384
    assert entry["source"] == config["source"] and entry["file"] == "benchmarks/configs/" + CONFIG + ".json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if '"name": "EvaByte"' in line)
        assert row["config"] == config["published"] and row["source_url"] == config["source"]


def test_a_position_reads_its_windows_rows_and_the_closed_windows_summaries(config):
    s = counts.shape(config)
    assert counts.rows_read(s, 0) == (1, 0) and counts.rows_read(s, 2047) == (2048, 0)
    assert counts.rows_read(s, 2048) == (1, 128) and counts.rows_read(s, 5000) == (5000 - 4096 + 1, 256)
    assert counts.rows_read(s, 14335) == (2048, 768)
    assert counts.row_flops(s) == 4 * 32 * 128 and counts.row_bytes(s, 2) == 2 * 32 * 128 * 2              # 16 KB of K and V a row
    # one more row read is one more row's scores and mix in each of the 8 layers
    assert counts.token_flops(s, 101) - counts.token_flops(s, 100) == 8 * 4 * 32 * 128
    assert counts.token_flops(s, 0) == 2 * 8 * counts.layer_matrix_params(s)
    assert counts.decode_flops(s, 5000) == counts.token_flops(s, 905 + 256) + 2 * 4096 * 2560


@pytest.mark.parametrize("n", [1, 15, 2048, 2049, 5000, 12288])
def test_a_prefill_is_the_sum_of_its_positions(config, n):
    s = counts.shape(config)
    by_position = sum(counts.token_flops(s, sum(counts.rows_read(s, p))) for p in range(n)) + counts.head_flops(s)
    assert counts.prefill_flops(s, n) == by_position


def test_a_step_needs_the_rows_read_and_no_other(config):
    s = counts.shape(config)
    flops, nbytes = counts.decode_step_need(s, 20, 20_000, 5_000, 2, 2, 2)
    # every weight; 25 000 rows read, 20 window rows and 2 summary rows written, at 16 KB a layer; 20 rows of 2560 float32 logits
    assert nbytes == counts.param_count(s) * 2 + 8 * 16384 * (25_000 + 20 + 2) + 20 * 2560 * 4
    assert flops == 20 * (2 * 8 * counts.layer_matrix_params(s) + 2 * 4096 * 2560) + 8 * 16384 * 25_000
    more = counts.decode_step_need(s, 20, 20_000, 5_001, 2, 2, 2)
    assert (more[0] - flops, more[1] - nbytes) == (8 * 16384, 8 * 16384)
    # bound by bytes: 6.5 GB against 0.07 TFLOP
    p = peaks.peaks_for("TPU v5 lite")
    assert nbytes / p["bytes_per_s"] > 7e-3 and flops / p["flops_per_s"] < 1e-3
    # the masked read of both tables whole would be 20 x 3072 rows: the need is under half of it here
    assert 25_000 < 0.5 * 20 * 3072


# ----------------------------------------------------------------- readers --
STEP = ("jit_step(7)", "jit_prefill(8)")


def _trace():
    """Two launches of the step (25 ms each) and one prefill."""
    ops = [["%fusion.1 = f32[20,4096]{1,0} fusion(...)", 0.000, 0.025], ["%fusion.1 = f32[20,4096]{1,0} fusion(...)", 0.030, 0.025]]
    return trace_reduce.Reduced({"0": ops}, {"0": [[STEP[0], 0.0, 0.025], [STEP[0], 0.030, 0.025], [STEP[1], 0.060, 0.030]]}, [])


def _spans(with_rows=True):
    first = {"eva_window_rows": 20_000, "eva_summary_rows": 5_000, "eva_chunks_closed": 2} if with_rows else {}
    second = {"eva_window_rows": 20_020, "eva_summary_rows": 5_128, "eva_chunks_closed": 0} if with_rows else {}
    return [("serve/step", 10.0, 10.028, dict({"iter": 1, "live": 20, "kv_write": "scatter", "attn_read": "masked"}, **first)),
            ("serve/step", 10.03, 10.058, dict({"iter": 2, "live": 20, "kv_write": "scatter", "attn_read": "masked"}, **second)),
            ("serve/prefill", 10.06, 10.09, dict({"iter": 3, "n": 1, "rows": 1, "bucket": 4096, "tokens": 3000},
                                                  **({"eva_windows": 2, "eva_chunks": 187} if with_rows else {})))]


class _Planned:
    def __init__(self, n):
        self.prompt = [0] * n


class _Record:
    def __init__(self, n0, token_at):
        self.planned, self.token_at = _Planned(n0), token_at


def _ctx(run, config, spans=(), trace=None, records=(), traced=(10.0, 10.1)):
    return run.Ctx(config=config, spans=list(spans), trace=trace, records=list(records), traced=traced, chips=1,
                   peaks=peaks.peaks_for("TPU v5 lite"))


def test_rows_needed_mean_reads_the_step_spans(run, config):
    ctx = _ctx(run, config, _spans())
    value = run.load_reader(BENCH, "eva_rows_needed_mean")(ctx)
    assert value == pytest.approx((25_000 / 20 + 25_148 / 20) / 2)
    assert ctx.notes["eva_rows_allocated"] == 2048 + 1024
    assert ctx.notes["eva_rows_needed_share"] == pytest.approx(value / 3072)


def test_decode_step_roofline_reads_the_rows_of_the_step_spans(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "decode_step_roofline.evabyte")(ctx)
    _, nbytes = counts.decode_step_need(counts.shape(config), 20, 20_010, 5_064, 1, 2, 2)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 25e-3) and value < 100.0
    assert ctx.notes["decode_roofline_bound"] == "bytes" and ctx.notes["decode_least_ms"] == pytest.approx(1e3 * nbytes / 819e9)


def test_mfu_counts_prompts_and_bytes_that_reached_a_client(run, config):
    s = counts.shape(config)
    records = [_Record(3000, [10.01, 10.05, 10.2]), _Record(5000, [9.0, 10.02])]      # one first byte and two later ones inside
    ctx = _ctx(run, config, [], _trace(), records)
    # byte k + 1 comes out of feeding byte k back at position n0 + k - 1
    flops = counts.prefill_flops(s, 3000) + counts.decode_flops(s, 3000) + counts.decode_flops(s, 5000)
    assert run.load_reader(BENCH, "mfu.serve.evabyte")(ctx) == pytest.approx(100.0 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no span, no trace", "a commit without these spans", "an empty trace"])
def test_where_there_is_nothing_to_read_a_reader_returns_none(run, config, name, what):
    if what == "no span, no trace":
        ctx = _ctx(run, config, traced=None)
    elif what == "a commit without these spans":            # serve/step without the eva_* rows: what the parent would leave
        ctx = _ctx(run, config, _spans(False), _trace())
    else:
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced({}, {}, []))
    value = run.load_reader(BENCH, name)(ctx)
    # the whole step's share counts the model's arithmetic from the clients' records: with a trace it reads 0 bytes, not None
    assert value is None or (name == "mfu.serve.evabyte" and value == 0.0)
    json.dumps(ctx.notes)


def test_every_new_metric_is_an_entry_a_file_and_reported_in_the_cell(run):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in NEW] == NEW                    # in this order, wherever later PRs append theirs
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, CELL, 1)
    reported = {m["name"] for m in run.metrics_for(manifest, cell, "per_layer")}
    assert set(NEW) <= reported and not {"mfu.serve", "decode_step_roofline", "mfu.serve.lfm2moe", "moe_expert_roofline"} & reported
    assert {m["name"] for m in run.metrics_for(manifest, cell, "end_to_end")} == {"tpot_p95_ms", "setup_s"}
    listed = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g] if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms" and callable(run.load_reader(BENCH, m["name"]))


# --------------------------------------------------------------- reference --
def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "evabyte.py")) as f:
        text = f.read()
    assert "bigdl_tpu" not in text and "import jax" in text


def test_the_controls_are_the_step_below_and_the_two_planted_faults(config):
    from benchmarks.reference import evabyte
    _, controls = evabyte.make(dict(config, constructor_kwargs=config["rehearse"]["constructor_kwargs"]))
    assert set(controls) == {"operands:float8_e4m3fn", "fault:no_summaries", "fault:uniform_chunks"}
    assert config["control"] == "operands:float8_e4m3fn"
    with pytest.raises(ValueError):
        evabyte.make(dict(config, faults=["no_such_fault"]))


def test_rehearsal_with_a_trace_fills_the_counter_and_no_device_metric(capsys):
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 32), "--seconds", "3", "--trace", "1", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"window_compiles"} and out["metrics"]["window_compiles"]["value"] == 0
    assert out["notes"]["checked"]["greedy_tokens"] >= 200


def test_a_planted_fault_makes_the_rehearsal_not_correct(capsys):
    """``--control`` puts the float8 control and both faults through the comparison at the rehearsal's size: the two faults, which
    leave the mechanism out, come out not correct by the widest gap (the rehearsal's prompts pass the first window of 32)."""
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", "7", "--seconds", "3", "--trace", "0", "--rehearse", "--control"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["controls"]) == {"operands:float8_e4m3fn", "fault:no_summaries", "fault:uniform_chunks"}
    for name in ("fault:no_summaries", "fault:uniform_chunks"):
        assert out["controls"][name]["correct_if_control"] is False
