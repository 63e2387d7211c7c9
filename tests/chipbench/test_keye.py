"""``family: keye`` (PR 32): the reference against a per-token loop written
here (selection, tie rule, the ``t + 1 <= topk`` case, M-RoPE sections),
the family's costs as integers, the cell's files against the issue's table,
its rehearsal, and the four new readers."""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, families, reference_keye  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "keye-longctx-closed"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config(rehearse=True):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       "keye-vl2-30b-a3b.json")))
    return cb_run._rehearsed(data, rehearse)


# ------------------------------------------- reference vs a per-token loop
TOY = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "rms_norm_eps": 1e-6, "rope_theta": 100.0,
       "rope_scaling": {"mrope_section": [1, 2, 1]},
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4,
                     "topk": 6}}


def _toy_params(seed, zero_indexer_weights=False):
    rng = np.random.default_rng(seed)
    d, h, kv, hd, hi, di, e, f, layers, v = 16, 4, 2, 8, 2, 4, 4, 8, 2, 32

    def n(*shape, s=0.3):
        return rng.normal(size=shape).astype(np.float32) * s

    blocks = {
        "attn_norm": 1 + n(layers, d, s=0.1), "q_w": n(layers, d, h * hd),
        "k_w": n(layers, d, kv * hd), "v_w": n(layers, d, kv * hd),
        "o_w": n(layers, h * hd, d), "q_norm": 1 + n(layers, hd, s=0.1),
        "k_norm": 1 + n(layers, hd, s=0.1),
        "mlp_norm": 1 + n(layers, d, s=0.1), "gate_w": n(layers, d, e),
        "experts_w1": n(layers, e, d, f), "experts_w3": n(layers, e, d, f),
        "experts_w2": n(layers, e, f, d), "idx_q_w": n(layers, d, hi * di),
        "idx_k_w": n(layers, d, di),
        "idx_w_w": np.zeros((layers, d, hi), np.float32)
        if zero_indexer_weights else n(layers, d, hi),
        "idx_k_norm": np.stack([1 + n(layers, di, s=0.1),
                                n(layers, di, s=0.1)], axis=1)}
    return {"embed": n(v, d, s=1.0), "blocks": blocks,
            "final_norm": 1 + n(d, s=0.1), "lm_head": n(d, v)}


def _loop_logits(cfg, params, tokens, positions):
    """The model of ``reference_keye``'s docstring, one token, one head and
    one key at a time, in numpy float64."""
    p = {k: (np.asarray(v, np.float64) if not isinstance(v, dict) else
             {kk: np.asarray(vv, np.float64) for kk, vv in v.items()})
         for k, v in params.items()}
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    sections = cfg["rope_scaling"]["mrope_section"]
    sa = cfg["sa_config"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    kx = cfg["num_experts_per_tok"]
    part = [c for c, width in enumerate(sections) for _ in range(width)]

    def rms(x, w):
        return x / math.sqrt((x * x).mean() + eps) * w

    def rope(x, pos3, by_section):
        half = len(x) // 2
        out = x.copy()
        for i in range(half):
            comp = part[i] if by_section else 0
            ang = pos3[comp] * theta ** (-2.0 * i / len(x))
            a, b = x[i], x[i + half]
            out[i] = a * math.cos(ang) - b * math.sin(ang)
            out[i + half] = b * math.cos(ang) + a * math.sin(ang)
        return out

    rows = []
    for row in range(tokens.shape[0]):
        s = tokens.shape[1]
        x = [p["embed"][tok] for tok in tokens[row]]
        pos = [positions[:, row, t] for t in range(s)]
        for layer in range(p["blocks"]["q_w"].shape[0]):
            w = {k: v[layer] for k, v in p["blocks"].items()}
            ys = [rms(xt, w["attn_norm"]) for xt in x]
            q = [[rope(rms((y @ w["q_w"])[j * hd:(j + 1) * hd], w["q_norm"]),
                       pos[t], True) for j in range(h)]
                 for t, y in enumerate(ys)]
            k = [[rope(rms((y @ w["k_w"])[g * hd:(g + 1) * hd], w["k_norm"]),
                       pos[t], True) for g in range(kv)]
                 for t, y in enumerate(ys)]
            v = [[(y @ w["v_w"])[g * hd:(g + 1) * hd] for g in range(kv)]
                 for y in ys]
            qi = [[rope((y @ w["idx_q_w"])[j * di:(j + 1) * di], pos[t],
                        False) for j in range(hi)] for t, y in enumerate(ys)]
            ki = []
            for t, y in enumerate(ys):
                raw = y @ w["idx_k_w"]
                normed = (raw - raw.mean()) / math.sqrt(raw.var() + 1e-6) \
                    * w["idx_k_norm"][0] + w["idx_k_norm"][1]
                ki.append(rope(normed, pos[t], False))
            wi = [y @ w["idx_w_w"] for y in ys]
            new = []
            for t in range(s):
                score = [sum(wi[t][j] * max(qi[t][j] @ ki[u], 0.0)
                             for j in range(hi)) for u in range(t + 1)]
                keys = list(range(t + 1))
                if t + 1 > topk:      # largest score; ties: the lower u
                    keys = sorted(sorted(keys, key=lambda u: (-score[u], u))
                                  [:topk])
                heads = []
                for j in range(h):
                    g = j // (h // kv)
                    att = np.array([q[t][j] @ k[u][g] / math.sqrt(hd)
                                    for u in keys])
                    pr = np.exp(att - att.max())
                    pr /= pr.sum()
                    heads.append(sum(pu * v[u][g]
                                     for pu, u in zip(pr, keys)))
                xt = x[t] + np.concatenate(heads) @ w["o_w"]
                z = rms(xt, w["mlp_norm"])
                logit = z @ w["gate_w"]
                prob = np.exp(logit - logit.max())
                prob /= prob.sum()
                top = sorted(range(len(prob)), key=lambda e: (-prob[e], e))[
                    :kx]
                total = sum(prob[e] for e in top)
                for e in top:
                    act = z @ w["experts_w1"][e]
                    act = act / (1 + np.exp(-act)) * (z @ w["experts_w3"][e])
                    xt = xt + prob[e] / total * (act @ w["experts_w2"][e])
                new.append(xt)
            x = new
        rows.append([rms(xt, p["final_norm"]) @ p["lm_head"] for xt in x])
    return np.asarray(rows)


@pytest.mark.parametrize("case", ["text", "three-position-components",
                                  "every-score-ties"])
def test_reference_agrees_with_a_per_token_loop(case):
    params = _toy_params(0, zero_indexer_weights=case == "every-score-ties")
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 32, (2, 14)).astype(np.int32)
    positions = np.broadcast_to(np.arange(14), (3, 2, 14)).copy()
    if case == "three-position-components":
        positions[1] = rng.integers(0, 9, (2, 14))
        positions[2] = rng.integers(0, 9, (2, 14))
    got = np.asarray(reference_keye.logits(TOY, params, tokens,
                                           positions=positions))
    want = _loop_logits(TOY, params, tokens, positions)
    np.testing.assert_allclose(got, want, atol=2e-4 * want.std())
    if case == "every-score-ties":
        # all scores 0: a query past topk attends the 6 LOWEST positions
        mask = np.asarray(reference_keye.selection_mask(
            np.zeros((9, 9), np.float32), np.tril(np.ones((9, 9), bool)), 6))
        assert mask[8].tolist() == [True] * 6 + [False] * 3
        assert mask[5].tolist() == [True] * 6 + [False] * 3  # t + 1 == topk
        assert mask[2].tolist() == [True] * 3 + [False] * 6  # under topk


def test_equal_position_components_are_the_one_dimensional_rotary():
    pos = np.broadcast_to(np.arange(11), (3, 2, 11))
    sectioned = np.asarray(reference_keye.mrope_angles(pos, 16, 1e4,
                                                       [2, 3, 3]))
    plain = np.asarray(reference_keye.mrope_angles(pos, 16, 1e4))
    np.testing.assert_array_equal(sectioned, plain)
    apart = pos.copy()
    apart[2] += 5                       # the width component alone
    moved = np.asarray(reference_keye.mrope_angles(apart, 16, 1e4, [2, 3, 3]))
    assert (moved[..., :5] == plain[..., :5]).all()
    assert (moved[..., 5:] != plain[..., 5:]).all()
    # the published sections cover the head's 64 pairs
    assert sum(_config(False)["rope_scaling"]["mrope_section"]) == 64


def test_reference_takes_the_engines_sets_and_holds_them_to_its_own():
    """What the bf16 comparison does (``drivers/serve_longctx.py``), at
    float32 where both sides choose alike: on the engine's own sets the
    reference gives its plain logits and reports full agreement; on other
    sets it gives other logits and says how far apart the sets are."""
    import jax

    import deepspeed_tpu
    from chipbench.drivers import serve_longctx

    cfg = _config()
    family = families.load(cfg)
    spec = family.build(cfg)
    params = spec.init_fn(jax.random.PRNGKey(0))
    srv = deepspeed_tpu.init_serving(
        spec, config={"dtype": "fp32"}, params=params, slots=2,
        max_seq_len=128, block_size=8, prefill_chunk=16)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 100)).astype(
        np.int32)
    got, chosen = serve_longctx.paged_choices(srv, tokens, 16)
    assert chosen["experts"].shape == (2, 2, 100, 4)
    # the comparison's own pool: 13 blocks of 8 keys, one bit a key
    assert chosen["keys"].shape == (2, 2, 100, 13)
    # a query attends min(ctx, topk) keys
    bits = np.unpackbits(chosen["keys"], axis=-1).sum(-1)
    assert (bits[0, 0] == np.minimum(np.arange(1, 101), 32)).all()
    plain = np.asarray(family.logits(cfg, params, tokens))
    forced, agreement = family.logits(cfg, params, tokens, forced=chosen)
    np.testing.assert_array_equal(np.asarray(forced), plain)
    assert agreement == {"keys": 1.0, "key_gap": 0.0, "experts": 1.0,
                         "expert_gap": 0.0}
    at = [15, 31, 47, 63, 79, 83] + list(range(84, 100))
    assert np.abs(got - plain[:, at]).max() < 1e-4 * plain.std()
    other = {"experts": (chosen["experts"] + 1) % 8,
             "keys": chosen["keys"] & 0x0F | 0x80}
    moved, agreement = family.logits(cfg, params, tokens, forced=other)
    assert np.abs(np.asarray(moved) - plain).max() > 0.05 * plain.std()
    assert agreement["keys"] < serve_longctx.KEY_AGREEMENT
    assert agreement["experts"] < serve_longctx.EXPERT_AGREEMENT
    assert agreement["key_gap"] > serve_longctx.KEY_GAP
    assert agreement["expert_gap"] > serve_longctx.EXPERT_GAP


# -------------------------------------------------------------------- costs
def test_costs_of_the_configuration_as_integers():
    cfg = _config(False)
    family = families.load(cfg)
    a = family.arch(cfg)
    assert (a["layers"], a["d"], a["heads"], a["kv_heads"], a["head_dim"]) \
        == (6, 2048, 32, 4, 128)
    assert family._layer_rest(a) + 128 * family._expert_params(a) \
        == 625_381_760
    assert family._layer_rest(a) == 18_874_368 + 4_352 + 262_144 + 2_261_120
    assert costs.num_params(cfg) == 4_374_622_464
    assert costs.weight_bytes(cfg) == 8_749_244_928
    assert costs.kv_bytes_per_token(cfg) == 12_288
    assert family.cached_bytes_per_token(cfg) == 13_056
    assert costs.active_params(cfg) == 4_374_622_464 \
        - 6 * 120 * 3 * 2048 * 768
    assert family.index_bytes(cfg, 7000) == 7000 * 6 * 64 * 2
    assert family.selected_kv_bytes(cfg, 2048) == 2048 * 6 * 2 * 4 * 128 * 2
    # every expert touched: all weights but the token table
    assert family.decode_weight_bytes(cfg, {"experts_touched_share": 1.0}) \
        == (4_374_622_464 - 151_936 * 2048) * 2
    # the program's own count, at the built depth and at the published one
    spec = family.build(cfg)
    assert spec.model_config.num_params() == 4_374_622_464
    assert spec.model_config.head_dim == 128
    assert spec.model_config.qk_norm == "head"
    assert spec.model_config.index_topk == 2048
    import dataclasses

    from deepspeed_tpu.models import mixtral

    preset = mixtral.MixtralConfig.keye_vl2_30b_a3b()
    built = dataclasses.replace(spec.model_config, num_layers=48)
    assert dataclasses.asdict(preset) == {
        **dataclasses.asdict(built),
        "router_aux_loss_coef": preset.router_aux_loss_coef}


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_holds_the_catalog_rows_numbers():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Keye-VL-2.0-30B-A3B")
    data = _config(False)
    assert data["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert data[key] == value, key
    assert data["reduced"] == ["depth"] and data["depth"] == 6


# ------------------------------------------------------------ the cell's files
def test_the_cells_files_say_what_the_issues_table_says():
    spec = cb_run.load_cell(CELL)
    mix, sizing = spec["traffic"], spec["sizing"]
    assert mix["kind"] == "serve_longctx" and mix["clients"] == 16
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 3072,
                                    "hi": 12288}
    assert mix["output_tokens"] == {"dist": "loguniform", "lo": 128,
                                    "hi": 512}
    assert mix["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (mix["deck"], mix["shared_prefix_tokens"]) == (32, 0)
    assert (mix["score_rows"], mix["score_tokens"]) == (2, 6144)
    assert mix["score_tokens"] == 3 * spec["config"]["sa_config"]["topk"]
    assert sizing["serving"] == {"slots": 16, "max_seq_len": 16384}
    assert spec["cell"]["chips"] == 1
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_tok_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {
        "decode_occupancy", "kv_pool_peak_used", "peak_hbm.serve",
        "device_idle.serve", "sched_host_share", "kv_host_ms",
        "expert_ffn_ms", "expert_ffn_roofline", "expert_rows_per_read",
        "sparse_attn_ms", "sparse_attn_roofline", "kv_selected_share",
        "prefill_select_ms", "kv_read_share"}
    # every prompt is past topk; the longest request fits its slot
    from chipbench import traffic
    deck = traffic.length_deck(mix)
    assert min(p for p, _ in deck) > 2048
    assert max(p + o for p, o in deck) <= 16384
    # the pool beside the weights: 12.2 GB of the chip's 16
    pool = (1 + 16 * 512) * 32 * families.load(
        spec["config"]).cached_bytes_per_token(spec["config"])
    assert pool == 3_422_969_856
    assert 0.25 * 16e9 < pool + costs.weight_bytes(spec["config"]) < 13e9


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
    # two thirds of the compared positions attend a selected set
    note = next(line for line in proc.stdout.splitlines()
                if "attend a selected set" in line)
    assert "2 x 96 tokens, 21 positions a row of which 19" in note
    share = result["metrics"]["kv_selected_share"]["value"]
    assert 15.0 < share < 100.0
    # whole blocks are fetched: never fewer rows than were chosen
    assert share <= result["metrics"]["kv_read_share"]["value"] <= 110.0


# ------------------------------------------------------------------ readers
READERS = cb_run.layer_metric_readers()
NEW = ("sparse_attn_ms", "sparse_attn_roofline", "kv_selected_share",
       "prefill_select_ms", "kv_read_share")


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
        _span("decode", 1.0, index_keys=10_000, kv_selected=4_096,
              kv_valid=10_000, sparse_rows=2, kv_read=9_984),
        _span("decode", 2.0, index_keys=12_000, kv_selected=4_096,
              kv_valid=12_000, sparse_rows=2, kv_read=11_008),
        _span("decode", 9.0, index_keys=1, kv_selected=1, kv_valid=1,
              sparse_rows=0),                        # outside the window
        _span("prefill", 1.5, index_keys=5, kv_selected=5, kv_valid=5,
              sparse_rows=0)])
    monkeypatch.setattr(program_trace, "kept", lambda name: ring)
    trace = {
        "programs": {"jit_decode_step": [0.01, 0.01], "jit_prefill": [0.02]},
        "custom_call_s": {
            "jit_decode_step:mosaic:paged_index_scores": 0.002,
            "jit_decode_step:mosaic:paged_sparse_select": 0.001,
            "jit_decode_step:mosaic:paged_sparse_attn": 0.003,
            "jit_decode_step:mosaic:decode_attn": 0.5,    # not the mechanism
            "jit_decode_step:mosaic:moe_gmm": 0.5,
            "jit_decode_step:mosaic:paged_decode_attn": 0.5,
            "jit_prefill:mosaic:paged_index_scores": 0.004,
            "jit_prefill:mosaic:paged_sparse_select": 0.006,
            "jit_prefill:mosaic:paged_sparse_attn": 0.010,
            "jit_prefill:mosaic:paged_prefill_attn": 0.5}}
    cfg = _config(False)
    ctx = {"trace": trace, "window": (0.5, 5.0), "counters": {},
           "config": cfg, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert READERS["sparse_attn_ms"](ctx) == pytest.approx(3.0)
    assert READERS["prefill_select_ms"](ctx) == pytest.approx(20.0)
    assert READERS["kv_selected_share"](ctx) == pytest.approx(
        100.0 * 8_192 / 22_000)
    assert READERS["kv_read_share"](ctx) == pytest.approx(
        100.0 * 20_992 / 22_000)
    needed = 11_000 * 6 * 64 * 2 + 4_096 * 12_288
    assert READERS["sparse_attn_roofline"](ctx) == pytest.approx(
        100.0 * needed / 819e9 / 0.003)
    # a later kernel of the mechanism joins the sum by its name
    fused = dict(trace["custom_call_s"])
    fused["jit_decode_step:mosaic:paged_sparse_fused"] = 0.004
    assert READERS["sparse_attn_ms"]({**ctx, "trace": {
        **trace, "custom_call_s": fused}}) == pytest.approx(5.0)
    # a model without the mechanism's kernels
    plain = {"programs": trace["programs"], "custom_call_s": {
        "jit_decode_step:mosaic:paged_decode_attn": 0.003}}
    assert READERS["sparse_attn_ms"]({**ctx, "trace": plain}) is None


def test_new_readers_find_nothing_on_an_empty_context(monkeypatch):
    from deepspeed_tpu.telemetry import trace as program_trace

    monkeypatch.setattr(program_trace, "kept", lambda name: None)
    empty = {"trace": None, "window": (0.0, 1.0), "counters": {},
             "config": _config(), "peaks": None}
    for name in NEW:
        assert READERS[name](empty) is None, name
    # a ring without the counters (the parent's) and a trace without the
    # kernels (the parent's)
    monkeypatch.setattr(program_trace, "kept", lambda name: _Ring(
        [_span("decode", 0.5, slots=3)]))
    parent = {**empty, "peaks": {"hbm_bytes_per_s": 819e9},
              "trace": {"programs": {"jit_decode_step": [0.01]},
                        "custom_call_s": {
                            "jit_decode_step:mosaic:paged_decode_attn": 1.0}}}
    for name in NEW:
        assert READERS[name](parent) is None, name


def test_benchmark_entries_of_this_family():
    entry = next(c for c in BENCH["configs"] if c["name"] == "keye-vl2-30b-a3b")
    assert entry["reduced"] == ["depth"]
    assert entry["file"] == "chipbench/configs/keye-vl2-30b-a3b.json"
    assert BENCH["configs"][-1] is entry and BENCH["workloads"][-1]["name"] \
        == CELL
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == [
        "sparse_attn_ms", "sparse_attn_roofline", "kv_selected_share",
        "prefill_select_ms", "kv_read_share"]
    for m in BENCH["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    for name in ("decode_roofline", "paged_attn_roofline", "ttft_p95_ms",
                 "itl_p95_ms"):
        metric = next(m for m in BENCH["per_layer"] + BENCH["end_to_end"]
                      if m["name"] == name)
        assert CELL not in metric["workloads"], name
