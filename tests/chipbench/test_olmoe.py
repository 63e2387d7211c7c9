"""``family: olmoe`` (PR 28): the reference against ``transformers``, the
program against the reference (full forward, and prefill + decode through
the paged cache), the cell's rehearsal, its three readers and its costs."""

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

from chipbench import costs, families, reference_olmoe  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "olmoe-decode-closed"
#: float32 program vs float32 reference on the same weights: both sides
#: are exact up to summation order (1e-6 measured).  A router in bf16 moves
#: top-k sets and weights by 2^-9 (1e-3 of the logit scale), renormalised
#: weights change every expert's share by a factor near 2 (checked below),
#: a per-head q/k-norm or a dropped expert lands far higher still.
REL_RMSE = 1e-4


def _config(rehearse=True):
    data = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       "olmoe-1b-7b.json")))
    return cb_run._rehearsed(data, rehearse)


def _rel_rmse(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def _seeded(cfg, seed=3):
    """The program's tiny model with every norm scale made to matter."""
    import jax

    model = families.load(cfg).build(cfg, {"use_flash": False})
    params = model.init_fn(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    return model, params


# ------------------------------------------------------ reference vs HF
def test_reference_agrees_with_transformers_olmoe():
    """``OlmoeForCausalLM`` at a tiny size, its random weights copied into
    the program's pytree layout: q/k-norm over all heads before the split,
    float32 softmax over every expert, top-k without renormalisation, an
    untied head."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    import jax.numpy as jnp

    cfg = _config()
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["depth"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], tie_word_embeddings=False,
        attention_dropout=0.0, clip_qkv=None)
    # fork_rng: the process's torch generator is left as it was (other
    # tests of this worker draw unseeded HF weights from it)
    with torch.random.fork_rng(), torch.no_grad():
        torch.manual_seed(0)
        hf = transformers.OlmoeForCausalLM(hf_cfg).float().eval()
        for name, p in hf.named_parameters():
            if "norm" in name:                 # ones at init: make them matter
                p.add_(0.2 * torch.randn_like(p))
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    n_layers, n_experts = cfg["depth"], cfg["num_experts"]

    def per_layer(fmt, transpose=True):
        rows = [sd[f"model.layers.{i}." + fmt] for i in range(n_layers)]
        return jnp.asarray(np.stack([r.T if transpose else r for r in rows]))

    def experts(proj):
        return jnp.asarray(np.stack([np.stack([
            sd[f"model.layers.{i}.mlp.experts.{e}.{proj}.weight"].T
            for e in range(n_experts)]) for i in range(n_layers)]))

    params = {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"]),
        "blocks": {
            "attn_norm": per_layer("input_layernorm.weight", False),
            "q_w": per_layer("self_attn.q_proj.weight"),
            "k_w": per_layer("self_attn.k_proj.weight"),
            "v_w": per_layer("self_attn.v_proj.weight"),
            "o_w": per_layer("self_attn.o_proj.weight"),
            "q_norm": per_layer("self_attn.q_norm.weight", False),
            "k_norm": per_layer("self_attn.k_norm.weight", False),
            "mlp_norm": per_layer("post_attention_layernorm.weight", False),
            "gate_w": per_layer("mlp.gate.weight"),
            "experts_w1": experts("gate_proj"),
            "experts_w3": experts("up_proj"),
            "experts_w2": experts("down_proj"),
        },
        "final_norm": jnp.asarray(sd["model.norm.weight"]),
        "lm_head": jnp.asarray(sd["lm_head.weight"].T),
    }
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (3, 19)).astype(np.int32)
    with torch.no_grad():
        want = hf(torch.tensor(ids.astype(np.int64))).logits.numpy()
    got = np.asarray(reference_olmoe.logits(cfg, params, ids))
    assert got.shape == want.shape
    assert _rel_rmse(got, want) < 1e-5, _rel_rmse(got, want)
    # the program's own uncached forward on HF's weights agrees as well
    from deepspeed_tpu.models import mixtral

    model = families.load(cfg).build(cfg, {"use_flash": False})
    ours = mixtral.forward_with_aux(model.model_config, params, ids,
                                    train=False)[0]
    assert _rel_rmse(ours, want) < REL_RMSE


# ------------------------------------------------- program vs reference
def test_programs_full_forward_agrees_with_the_reference():
    import jax

    from deepspeed_tpu.models import mixtral

    cfg = _config()
    fam = families.load(cfg)
    model, params = _seeded(cfg)
    assert model.model_config.qk_norm and model.model_config.top_k == 4
    assert model.model_config.num_params() == costs.num_params(cfg)
    assert model.model_config.active_params() == costs.active_params(cfg)
    ids = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (3, 21)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = mixtral.forward_with_aux(model.model_config, params, ids,
                                       train=False)[0]
        loss = float(model.loss_fn(params, {"input_ids": ids}, train=False))
    want = fam.logits(cfg, params, ids)
    assert _rel_rmse(got, want) < REL_RMSE, _rel_rmse(got, want)
    at = [0, 7, 20]
    np.testing.assert_allclose(np.asarray(fam.logits(cfg, params, ids, at=at)),
                               np.asarray(want)[:, at], rtol=1e-5, atol=1e-5)
    assert abs(float(fam.next_token_loss(cfg, params, ids)) - loss) < 1e-4
    # the tolerance can tell: renormalised router weights are another model
    other = fam.logits({**cfg, "norm_topk_prob": True}, params, ids)
    assert _rel_rmse(other, want) > 100 * REL_RMSE


def test_prefill_then_paged_decode_agrees_with_the_reference():
    """Through ``init_serving``: the engine's weights, paged pool, chunked
    prefill and decode hooks, teacher-forced as the cell's set-up does."""
    import deepspeed_tpu
    from chipbench.drivers import serve_closed

    cfg = _config()
    fam = families.load(cfg)
    model, params = _seeded(cfg, seed=5)
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        model, config={"dtype": "fp32"}, params=params, slots=3,
        max_seq_len=64, block_size=8, prefill_chunk=16)
    n_decode, s = 6, 2 * 16 + 6
    ids = np.random.default_rng(2).integers(
        0, cfg["vocab_size"], (3, s)).astype(np.int32)
    got = serve_closed.paged_logits(srv, ids, n_decode)
    at = [15, 31] + list(range(32, s))
    want = fam.logits(cfg, srv.engine.params, ids, at=at)
    assert got.shape == np.asarray(want).shape
    assert _rel_rmse(got, want) < REL_RMSE, _rel_rmse(got, want)
    srv.close()


# ------------------------------------------------------------ the cell
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_is_correct(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 4242), "--seconds", "1",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    if trace:
        # 4 live rows x top-4 of 8 experts: between 1 and 4 rows a read
        assert 1.0 <= res["metrics"]["expert_rows_per_read"]["value"] <= 4.0
        assert "expert_ffn_ms" not in res["metrics"]      # no device trace
    else:
        assert set(res["metrics"]) == {"setup_s", "serve_tok_s"}


def test_the_cells_files_say_what_the_issue_asked_for():
    spec = cb_run.load_cell(CELL)
    cfg = spec["config"]
    assert cfg["family"] == "olmoe" and cfg["reduced"] == ["depth"]
    assert (cfg["num_hidden_layers"], cfg["depth"]) == (16, 8)
    assert spec["sizing"]["serving"] == {"slots": 64, "max_seq_len": 1024}
    mix = spec["traffic"]
    assert mix["kind"] == "serve_closed" and mix["clients"] == 64
    assert mix["shared_prefix_tokens"] == 0 and mix["deck"] == 64
    from chipbench import traffic

    deck = traffic.length_deck(mix)
    assert 32 <= min(p for p, _ in deck) and max(p for p, _ in deck) <= 256
    assert 128 <= min(o for _, o in deck) and max(o for _, o in deck) <= 704
    assert max(p + o for p, o in deck) <= 1024
    reported = {m["name"] for m in spec["per_layer"]}
    assert {"expert_ffn_ms", "expert_ffn_roofline", "expert_rows_per_read",
            "paged_attn_roofline", "decode_roofline",
            "peak_hbm.serve"} <= reported


# ----------------------------------------------------------------- costs
def test_costs_of_the_configuration_are_these_integers():
    cfg = _config(rehearse=False)
    a = costs.arch(cfg)
    assert (a["layers"], a["d"], a["heads"], a["kv_heads"], a["head_dim"],
            a["ffn"], a["experts"], a["top_k"], a["vocab"]) == \
        (8, 2048, 16, 16, 128, 1024, 64, 8, 50304)
    expert = 3 * 2048 * 1024
    per_layer = 4 * 2048 * 2048 + 2 * 2048 + 2 * 2048 + 2048 * 64 \
        + 64 * expert
    assert per_layer == 419_569_664
    n = 2 * 50304 * 2048 + 8 * per_layer + 2048
    assert costs.num_params(cfg) == n == 3_562_604_544
    assert costs.weight_bytes(cfg) == 7_125_209_088
    assert costs.kv_bytes_per_token(cfg) == 2 * 8 * 16 * 128 * 2 == 65_536
    assert costs.active_params(cfg) == n - 8 * 56 * expert == 744_032_256
    assert costs.train_flops_per_token(cfg, 1024) == \
        6.0 * 744_032_256 + 12.0 * 8 * 2048 * 1024
    fam = families.load(cfg)
    experts_all = 8 * 64 * expert * 2
    rest = (n - 50304 * 2048 - 8 * 64 * expert) * 2
    assert (experts_all, rest) == (6_442_450_944, 476_712_960)
    assert fam.expert_bytes_touched(cfg, {"experts_touched_share": 1.0}) \
        == experts_all
    assert fam.decode_weight_bytes(cfg, {"experts_touched_share": 1.0}) \
        == rest + experts_all == 6_919_163_904
    assert fam.decode_weight_bytes(cfg, {"experts_touched_share": 0.75}) \
        == rest + 0.75 * experts_all
    assert costs.decode_bytes_per_step(
        cfg, 1000.0, {"experts_touched_share": 0.5}) == \
        rest + 0.5 * experts_all + 65_536 * 1000.0


def test_touched_share_is_read_from_the_programs_ring(monkeypatch):
    cfg = _config(rehearse=False)
    fam = families.load(cfg)
    from chipbench.layer_metrics import _program_spans as ps

    def decode(touched, **more):
        return {"ph": "X", "name": "decode", "ts": 0.0, "dur": 1.0,
                "args": {"experts_touched": touched, **more}}

    ring = [decode(512), decode(256),
            {"ph": "X", "name": "prefill", "ts": 0.0, "dur": 1.0,
             "args": {"experts_touched": 8}}]
    monkeypatch.setattr(ps, "serve_ring", lambda: (ring, 0.0, 0))
    assert fam.expert_bytes_touched(cfg, {}) == 384 * 3 * 2048 * 1024 * 2
    monkeypatch.setattr(ps, "serve_ring", lambda: None)    # no ring: all
    assert fam.expert_bytes_touched(cfg, {}) == 6_442_450_944


# --------------------------------------------------------------- readers
def _ctx(trace, ring, monkeypatch):
    from chipbench import spans
    from chipbench.layer_metrics import _program_spans as ps

    monkeypatch.setattr(ps, "serve_ring", lambda: ring)
    return {"trace": trace, "peaks": {"hbm_bytes_per_s": 819e9},
            "counters": {"mean_valid_kv_tokens": 1000.0}, "samples": {},
            "device": {"memory_peak_bytes": 0}, "window": (10.0, 20.0),
            "spans": spans.Spans(), "config": _config(rehearse=False)}


def test_the_three_readers_on_a_hand_made_context(monkeypatch):
    readers = cb_run.layer_metric_readers()
    trace = {"programs": {"jit_decode_step": [0.06] * 10,
                          "jit_prefill": [0.02] * 3},
             "custom_call_s": {
                 "jit_decode_step:mosaic:moe_gmm": 0.100,
                 "jit_decode_step:mosaic:paged_decode_attn": 0.400,
                 "jit_prefill:mosaic:moe_gmm": 0.050}}

    def span(name, t0, **args):
        return {"ph": "X", "name": name, "ts": t0 * 1e6, "dur": 5e4,
                "args": args}

    ring = ([span("decode", 5.0, experts_touched=100, expert_rows=100),
             span("decode", 11.0, experts_touched=512, expert_rows=4096),
             span("decode", 12.0, experts_touched=488, expert_rows=3904),
             span("prefill", 13.0, experts_touched=512, expert_rows=9999),
             span("decode", 21.0, experts_touched=7, expert_rows=7)],
            0.0, 0)
    ctx = _ctx(trace, ring, monkeypatch)
    assert readers["expert_ffn_ms"](ctx) == pytest.approx(10.0)   # 0.1 s / 10
    # the ring's decode spans read 512, 488 (and 100, 7 outside the window;
    # the family reads all four): mean 276.75 of 512 sets x 12.58 MB
    touched = (100 + 512 + 488 + 7) / 4 * 3 * 2048 * 1024 * 2
    assert readers["expert_ffn_roofline"](ctx) == pytest.approx(
        100.0 * (touched / 819e9) / 0.010)
    assert readers["expert_rows_per_read"](ctx) == pytest.approx(8.0)
    # XLA's own grouped matmul is read under the name the trace gives it
    ragged = {"programs": trace["programs"], "custom_call_s": {
        "jit_decode_step:mosaic:ragged-dot-none": 0.03,
        "jit_decode_step:mosaic:ragged-dot-metadata": 0.01}}
    assert readers["expert_ffn_ms"](_ctx(ragged, ring, monkeypatch)) \
        == pytest.approx(4.0)


def test_the_three_readers_return_nothing_on_an_empty_context(monkeypatch):
    readers = cb_run.layer_metric_readers()
    names = ("expert_ffn_ms", "expert_ffn_roofline", "expert_rows_per_read")
    empty = _ctx(None, None, monkeypatch)          # no trace, no ring
    for name in names:
        assert readers[name](empty) is None, name
    # a dense family's trace and ring: no grouped matmul, no routing
    dense = _ctx({"programs": {"jit_decode_step": [0.1]},
                  "custom_call_s": {
                      "jit_decode_step:mosaic:paged_decode_attn": 0.05}},
                 ([{"ph": "X", "name": "decode", "ts": 11e6, "dur": 1e4,
                    "args": {"slots": 3}}], 0.0, 0), monkeypatch)
    for name in names:
        assert readers[name](dense) is None, name
