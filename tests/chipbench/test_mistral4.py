"""``family: mistral4`` (PR 39): the configuration file against the catalog
row and the cut it states, the family's costs as integers, the cell's files
against the issue's table, its rehearsal, the five new readers, the
reference's variants and the controls, and the benchmark's entries — every
entry looked up BY NAME, so that the next cell does not turn this red."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, families, reference_mistral4  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "mistral4-longdecode-closed"
NAME = "mistral-small-4-119b-2603"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("latent_attn_ms", "latent_attn_roofline", "latent_prefill_ms",
       "latent_prefill_roofline", "kv_block_fill")
JOINED = ("serve_tok_s", "decode_occupancy", "kv_pool_peak_used",
          "kv_host_ms", "sched_host_share", "peak_hbm.serve",
          "device_idle.serve", "expert_ffn_ms", "expert_ffn_roofline",
          "expert_rows_per_read", "prefill_chunk_ms.longprompt")


def _config(rehearse=True):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       NAME + ".json")))
    return cb_run._rehearsed(data, rehearse)


# ------------------------------------------------------- the configuration
def test_configuration_states_the_cut_and_the_published_counts():
    data = _config(False)
    assert data["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert (data["depth"], data["num_hidden_layers"]) == (6, 36)
    assert (data["n_routed_experts"], data["n_routed_experts_published"],
            data["experts_first"]) == (16, 128, 0)
    assert (data["vocab_size"], data["vocab_size_published"]) \
        == (16384, 131072)
    # the floors: a period (one layer) and four more, 8 experts, an eighth
    # of the vocabulary
    assert data["first_k_dense_replace"] == 0 and data["depth"] >= 5
    assert data["n_routed_experts"] >= 8
    assert data["vocab_size"] * 8 >= data["vocab_size_published"]
    # no width moved
    assert (data["hidden_size"], data["num_attention_heads"],
            data["q_lora_rank"], data["kv_lora_rank"],
            data["qk_nope_head_dim"], data["qk_rope_head_dim"],
            data["v_head_dim"], data["moe_intermediate_size"],
            data["num_experts_per_tok"], data["n_shared_experts"]) \
        == (4096, 32, 1024, 256, 64, 64, 128, 2048, 4, 1)
    assert data["rope_parameters"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
    assert "eight v5e chips share each layer" in data["deployment"]
    assert "48 chips" in data["deployment"]
    assert "2,872,634,880 parameters" in data["deployment"]
    assert "3,840 B" in data["deployment"]
    for key in ("depth", "n_routed_experts", "vocab_size", "softmax_scale",
                "rope", "query_temperature", "latent_norms", "router",
                "shared_expert", "deployment_layout", "weights",
                "vision_encoder"):
        assert key in data["assumed"], key
    assert data["dtype"] == "bf16" and data["family"] == "mistral4"
    assert len(data["source"]) <= 200
    # none of the reduced keys reads as a width
    import re
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size"
                       r"|_dim$|_rank$|head_size|expansion|per_tok")
    assert not any(width.search(k) for k in data["reduced"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mistral-Small-4-119B-2603")
    data = _config(False)
    assert data["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in data["reduced"]:
            assert data[key + "_published"] == value, key
        else:
            assert data[key] == value, key
    assert data["num_hidden_layers"] == row["layers"]


# -------------------------------------------------------------------- costs
def test_costs_of_the_configuration_as_integers():
    cfg = _config(False)
    family = families.load(cfg)
    a = family.arch(cfg)
    assert (a["layers"], a["d"], a["heads"], a["kv_heads"], a["head_dim"],
            a["vocab"]) == (6, 4096, 32, 32, 128, 16384)
    assert family._expert_params(a) == 25_165_824
    # W_dq + q norm + W_uq + W_dkv + kv norm + W_ukv + W_o, two block
    # norms, the router, the shared expert: "about 54M"
    assert family._layer_rest(a) == (
        4_194_304 + 1_024 + 4_194_304 + 1_310_720 + 256 + 1_572_864
        + 16_777_216) + 8_192 + 524_288 + 25_165_824 == 53_748_992
    assert costs.num_params(cfg) == 2_872_634_880
    assert costs.weight_bytes(cfg) == 5_745_269_760
    assert family.latent_bytes_per_key(cfg) == 640
    assert family.cached_bytes_per_token(cfg) == 3_840
    # what costs.py would reckon for the expanded form: 25.6 x as much
    assert costs.kv_bytes_per_token(cfg) == 98_304
    assert family.latent_flops_per_key(cfg) == 2 * 32 * (320 + 256)
    # top-4 of 128, 16 held: half a held expert a token a layer
    assert costs.active_params(cfg) == 2_872_634_880 \
        - int(6 * 15.5 * 25_165_824)
    assert family.expert_bytes_touched(cfg, {"experts_touched_share": 0.5}) \
        == 48 * 25_165_824 * 2
    # every held expert touched: all weights but the token table
    assert family.decode_weight_bytes(cfg, {"experts_touched_share": 1.0}) \
        == 5_745_269_760 - 2 * 16384 * 4096
    spec = family.build(cfg)
    mc = spec.model_config
    assert mc.num_params() == 2_872_634_880
    assert (mc.num_experts, mc.experts_held, mc.shared_experts, mc.top_k) \
        == (128, (0, 16), 1, 4)
    assert (mc.norm, mc.parallel_block, mc.rope_interleaved,
            mc.router_score, mc.tie_embeddings, mc.layer_kinds) \
        == ("rms", False, True, "softmax", False, ())
    import dataclasses

    from deepspeed_tpu.models import mixtral

    preset = mixtral.MixtralConfig.mistral_small_4()
    built = dataclasses.replace(mc, num_layers=36, vocab_size=131072,
                                experts_held=None)
    assert dataclasses.asdict(preset) == dataclasses.asdict(built)
    assert spec.decode_hooks["latent_attention"] == {
        "rank": 256, "rope": 64, "width": 320}


def test_family_meets_the_contract():
    cfg = _config()
    family = families.load(cfg)
    for fn in families.REQUIRED + ("active_params", "decode_weight_bytes",
                                   "expert_bytes_touched",
                                   "cached_bytes_per_token",
                                   "latent_bytes_per_key",
                                   "latent_flops_per_key"):
        assert callable(getattr(family, fn)), fn
    assert set(families.SIZES) <= set(costs.arch(cfg))


# ------------------------------------------------------------ the cell's files
def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]
    assert mix["kind"] == "serve_latent" and mix["clients"] == 64
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096,
                                    "hi": 12288}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 384,
                                    "hi": 1536}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["shared_prefix_tokens"]) == (96, 0)
    # the driver's, not the users': how long the loop runs before the
    # window opens, and why (the longest reply: 1,536 tokens at ~40 ms)
    assert mix["settle_s"] == 60 and "settle_s" in mix["why"]
    assert (mix["score_rows"], mix["score_tokens"]) == (2, 10240)
    original = spec["config"]["rope_parameters"][
        "original_max_position_embeddings"]
    assert 4 * mix["score_tokens"] == 5 * original
    assert sizing["serving"] == {"slots": 64, "max_seq_len": 16384}
    # no option beyond the issue's table: the engine derives the block of
    # a latent pool, and the note holds the measurement behind the rule
    from deepspeed_tpu.ops import paged_kv
    block = paged_kv.latent_block_tokens(320, 2, 16384)
    assert block == 512 and "us a visit" in sizing["note"]
    assert spec["cell"]["chips"] == 1 and len(spec["cell"]["why"]) <= 200
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tok_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {*JOINED[1:], *NEW}
    from chipbench import traffic
    deck = traffic.length_deck(mix)
    assert len(deck) == 96
    assert min(p for p, _ in deck) >= 4096
    assert max(p + o for p, o in deck) <= 16384
    # the pool beside the weights: 10.6 GB of the chip's 16
    pool = (1 + 64 * (16384 // block)) * block * 384 * 2 * 6
    total = pool + costs.weight_bytes(spec["config"])
    assert 4.8e9 < pool < 4.9e9 and 0.25 * 16e9 < total < 11e9
    # the expanded K and V of the same slots would be 103 GB
    assert 64 * 16384 * costs.kv_bytes_per_token(spec["config"]) > 100e9


def test_rehearsal_of_the_cell_is_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    note = next(line for line in proc.stdout.splitlines()
                if "lie past the original context" in line)
    assert "2 x 64 tokens at block 16, 19 positions a row of which 17" \
        in note
    metrics = result["metrics"]
    assert 50.0 < metrics["kv_block_fill"]["value"] <= 100.0
    assert 0.0 < metrics["kv_pool_peak_used"]["value"] <= 100.0
    assert metrics["expert_rows_per_read"]["value"] > 0
    detail = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("chipbench: detail ")).split("detail ", 1)[1])
    assert detail["counters"]["block_size"] == 16
    assert detail["counters"]["num_blocks"] == 1 + 4 * 8
    # the settled start: the loop ran on, unmeasured, between the callers'
    # first tokens and the window, and the set-up carries it
    settled = next(line for line in proc.stdout.splitlines()
                   if line.startswith("chipbench: settled "))
    assert float(settled.split()[2]) >= 0.5 and "requests ended" in settled
    spans = json.loads(next(
        line for line in proc.stdout.splitlines()
        if line.startswith("chipbench: set-up spans")).split(
            "(s): ", 1)[1].split("; compiles")[0])
    assert spans["cb.setup.settle"] >= 0.5 and spans["cb.setup.warm_in"] > 0
    assert detail["setup_s"] > sum(spans.values()) - 0.1


# ------------------------------------------------------------------ readers
READERS = cb_run.layer_metric_readers()


class _Ring:
    epoch_s, dropped = 0.0, 0

    def __init__(self, events):
        self._events = events

    def events(self):
        return self._events


def _span(name, t0_s, **args):
    return {"ph": "X", "name": name, "ts": t0_s * 1e6, "dur": 1e3,
            "args": args}


def test_new_readers_on_a_hand_made_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    ring = _Ring([
        _span("decode", 1.0, kv_valid=3_000_000, kv_pairs=3_000_000,
              kv_blocks=1_000, latent_bytes=1_920_000_000),
        _span("decode", 2.0, kv_valid=2_000_000, kv_pairs=2_000_000,
              kv_blocks=700, latent_bytes=1_280_000_000),
        _span("decode", 9.0, kv_valid=1, kv_pairs=1, kv_blocks=1,
              latent_bytes=640),                       # outside the window
        _span("prefill", 1.5, kv_valid=90_000, kv_pairs=11_000_000,
              kv_blocks=40, latent_bytes=57_600_000)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.02, 0.02], "jit_prefill": [0.03]},
        "custom_call_s": {
            "jit_decode_step:mosaic:paged_latent_attn": 0.012,
            "jit_decode_step:mosaic:paged_latent_verify": 0.002,
            "jit_decode_step:mosaic:moe_gmm": 0.5,       # not the walk
            "jit_prefill:mosaic:paged_latent_prefill": 0.005,
            "jit_prefill:mosaic:moe_gmm": 0.5}}
    cfg = _config(False)
    ctx = {"trace": trace, "window": (0.5, 5.0),
           "counters": {"block_size": 512}, "config": cfg,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    assert READERS["latent_attn_ms"](ctx) == pytest.approx(7.0)
    assert READERS["latent_prefill_ms"](ctx) == pytest.approx(5.0)
    # one query a row: the bytes bound (1.6 GB / 819 GB/s = 1.95 ms against
    # 2.5 M pairs x 36,864 FLOPs / 197 TFLOP/s = 0.47 ms)
    assert READERS["latent_attn_roofline"](ctx) == pytest.approx(
        100.0 * 1.6e9 / 819e9 / 0.007)
    # a chunk: the FLOPs bound
    assert READERS["latent_prefill_roofline"](ctx) == pytest.approx(
        100.0 * 11e6 * 36_864 / 197e12 / 0.005)
    assert READERS["kv_block_fill"](ctx) == pytest.approx(
        100.0 * 5_000_000 / (1_700 * 512 * 6))
    # a family without the functions: no share of a roofline
    other = {**ctx, "config": {**cfg, "family": "olmoe"}}
    assert READERS["latent_attn_roofline"](other) is None
    assert READERS["latent_prefill_roofline"](other) is None


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the counters and a trace without the kernels (any
    # other model; the parent of PR 39)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3, expert_rows=5, experts_touched=2),
         _span("prefill", 0.6, kv_blocks=4, rows=2)]))
    parent = {**empty, "counters": {"block_size": 32},
              "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
              "trace": {"programs": {"jit_decode_step": [0.01],
                                     "jit_prefill": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:paged_decode_attn": 1.0,
                            "jit_prefill:mosaic:paged_prefill_attn": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


def test_controls_each_shortcut_is_refused_by_the_comparison(tmp_path):
    """The harness mode PERF.md's table of controls is made with, at the
    rehearsal's widths: the plain reference passes ``check_logits`` and
    every shortcut variant comes out ``ok: false`` — ``t(p)`` dropped
    passing below the original context and failing past it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.drivers.serve_latent",
         "--workload", CELL, "--seed", "2147483999", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    assert lines[-1] == {"controls_held": True}
    assert [(c["variant"], c["ok"]) for c in lines[:-1]] == [
        (None, True)] + [(v, False) for v in reference_mistral4.VARIANTS[1:]]
    flat = next(c for c in lines if c["variant"] == "no_temperature")
    assert flat["logit_rel_rmse_below"] <= flat["tolerance"] \
        < flat["logit_rel_rmse_past"]
    assert all(c["positions_past"] == 34 for c in lines[:-1])


# --------------------------------------------------- the reference's variants
@pytest.mark.parametrize("variant", reference_mistral4.VARIANTS[1:])
def test_each_shortcut_variant_moves_the_reference(variant):
    """The variants the comparison is checked with (PERF.md section 6) are
    different functions: at tiny widths each moves the logits by far more
    than rounding, or (``router_fp8``) the expert sets."""
    cfg = _config()
    rng = np.random.default_rng(0)
    import jax

    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim > 1 else a,
        families.load(cfg).build(cfg).init_fn(jax.random.PRNGKey(1)))
    tokens = rng.integers(0, cfg["vocab_size"], (1, 48)).astype(np.int32)
    want = np.asarray(reference_mistral4.logits(cfg, params, tokens))
    got = np.asarray(reference_mistral4.logits(cfg, params, tokens,
                                               variant=variant))
    rel = float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
    # (the temperature is 1.07 on the 16 of 48 positions past the tiny
    # original context, and 1 before it)
    assert rel > (0.005 if variant == "no_temperature" else 0.02), \
        (variant, rel)
    with pytest.raises(ValueError, match="variant"):
        reference_mistral4.hidden_states(cfg, params, tokens,
                                         variant="no_such")


def test_forced_sets_report_how_far_the_two_sides_choices_lie_apart():
    """``logits(forced=)``: on its own sets the reference agrees wholly and
    no expert lies apart; with one token's last expert swapped for the
    next-best, one of its ``k`` is outside the forced set and the mean and
    the largest distance are that one expert's."""
    import jax
    import jax.numpy as jnp

    cfg = _config()
    params = jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim > 1 else a,
        families.load(cfg).build(cfg).init_fn(jax.random.PRNGKey(1)))
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    k, layers = cfg["num_experts_per_tok"], cfg["depth"]
    # the reference's own sets, layer by layer: run it forced on a guess,
    # which moves nothing the router sees in layer 0
    guess = {"experts": jnp.zeros((layers, 1, 24, k), jnp.int32)
             + jnp.arange(k, dtype=jnp.int32)}
    plain = np.asarray(reference_mistral4.logits(cfg, params, tokens))
    _, far = reference_mistral4.logits(cfg, params, tokens, forced=guess)
    assert 0.0 <= far["experts"] < 1.0
    assert 0.0 < far["expert_gap"] <= far["expert_gap_max"] <= 1.0
    # own sets: found by scoring layer 0's router by hand is the driver's
    # job; here the float32 program's choices are the reference's
    spec = families.load(cfg).build(cfg)
    from deepspeed_tpu.ops import paged_kv
    cache = spec.decode_hooks["init_cache"](1 + 3, 8, jnp.float32)
    bt = jnp.asarray([[1, 2, 3]], jnp.int32)
    _, _, chosen = spec.decode_hooks["forward_cached"](
        params, jnp.asarray(tokens), paged_kv.pack_pool(cache),
        jnp.zeros((1,), jnp.int32), lengths=jnp.full((1,), 24, jnp.int32),
        block_tables=bt, choices=True)
    same, own = reference_mistral4.logits(cfg, params, tokens,
                                          forced=chosen)
    np.testing.assert_allclose(same, plain, atol=2e-4)
    assert own == {"experts": 1.0, "expert_gap": 0.0, "expert_gap_max": 0.0,
                   "expert_gap_max_by_layer": [0.0] * layers}


# ------------------------------------------------------ the benchmark's entries
def _named(key, name):
    return next(e for e in BENCH[key] if e["name"] == name)


def test_benchmark_entries_of_this_family():
    """Looked up BY NAME, never by position: a later PR appends behind
    these and this stays green."""
    entry = _named("configs", NAME)
    assert entry["reduced"] == ["depth", "n_routed_experts", "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cell = _named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "longdecode-closed", 1)
    for word in ("64 callers", "4,096-12,288", "384-1,536", "1/8",
                 "6 of 36 layers"):
        assert word in cell["why"], word
    for name in NEW:
        m = _named("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert {n: _named("per_layer", n)["layer"] for n in NEW} == {
        "latent_attn_ms": "model step", "latent_attn_roofline": "kernels",
        "latent_prefill_ms": "model step",
        "latent_prefill_roofline": "kernels", "kv_block_fill": "KV manager"}
    # in the lists the issue names and in no other
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if m["name"] in JOINED + NEW:
            assert CELL in m["workloads"], m["name"]
        else:
            assert CELL not in m.get("workloads", ()), m["name"]
    assert BENCH["run_seconds"] == 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
