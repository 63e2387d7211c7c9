"""``chip_smoke.py`` cannot rot: its phases run here at tiny widths on the
forced-CPU platform (Pallas kernels interpreted), its entry point refuses a
machine without a TPU, a failing phase fails the run, and importing the
package leaves the chip to whoever needs it."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def _tiny(smoke):
    from deepspeed_tpu.models import gpt2, opt

    g = gpt2.GPT2Config.tiny(vocab_size=512, max_seq_len=64)
    g.remat, g.use_flash, g.remat_policy = True, True, "dots_flash"
    g.scan_layers = False
    # K = 128 so the w8a8 records and the s8 kernels are really exercised;
    # 8 heads so the pool shards over the suite's 8 virtual devices — the
    # script's several-chips path (topology=n, dp=n) runs here too
    o = opt.OPTConfig(vocab_size=512, max_seq_len=128, num_layers=2,
                      num_heads=8, hidden_size=128, ffn_size=256)
    from deepspeed_tpu.models import mixtral

    # head_dim 128 (the g = 1 branch of the packed pool), top-4 of 8
    moe = mixtral.MixtralConfig(
        vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2,
        num_kv_heads=2, hidden_size=256, ffn_size=64, num_experts=8,
        top_k=4, norm_topk_prob=False, qk_norm=True)
    return smoke.Sizes(
        opt=o, gpt2=g, moe=moe, dtype="bf16", prompt_lens=(3, 20, 40),
        shared_prefix=16, new_tokens=(4, 6), score_len=40, score_decode=8,
        micro_bs=2, seq=32, gas=2, sync_dim=128, sync_iters=4,
        sampler_vocab=1000,
        serving_kwargs={"block_size": 8, "prefill_chunk": 16})


@pytest.mark.limit(360)   # every smoke kernel, interpreted: 110 s of 152 (PR 43)
def test_phases_run_at_tiny_widths_on_cpu(smoke):
    ok, report = smoke.run_phases(_tiny(smoke))
    assert ok, report["phases"]
    assert list(report["phases"]) == ["device", "kernels", "serve",
                                      "serve-q", "serving-memory", "train"]
    assert "serving_memory" not in report       # a TPU-only check
    assert report["device"]["platform"] == "cpu"
    # interpreted kernels: no Mosaic call may be claimed off the chip
    assert report["serve_bf16"]["mosaic_calls"] == 0
    assert report["serve_bf16"]["compile_count"] <= \
        report["serve_bf16"]["compile_budget"]
    assert report["serve_w8a8+kv8"]["logit_rmse"] <= smoke.QUANT_LOGIT_RMSE
    losses = report["train"]["losses"]
    assert losses[2] < losses[0]


def test_failing_phase_fails_the_run(smoke, monkeypatch, capsys, tmp_path):
    def boom(sz, report):
        raise RuntimeError("kernel refused by the compiler")

    seen = []
    ok, report = smoke.run_phases(
        None, phases=[("boom", boom), ("after", lambda s, r: seen.append(1))])
    assert not ok and seen == [1]          # later phases still report
    assert report["phases"]["boom"].startswith("RuntimeError: kernel refused")
    capsys.readouterr()

    # the entry point turns that into a non-zero exit and no result line
    import jax

    from deepspeed_tpu.utils import platform

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU test")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setattr(platform, "enable_compile_cache",
                        lambda root: str(tmp_path))
    monkeypatch.setattr(smoke, "REPO_ROOT", str(tmp_path))
    monkeypatch.setattr(smoke, "full_sizes", lambda: None)
    monkeypatch.setattr(smoke, "PHASES", [("boom", boom)])
    assert smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out
    monkeypatch.setattr(smoke, "PHASES", [("fine", lambda s, r: None)])
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU test", "count": 1}}


def _run(args, **env):
    base = {k: v for k, v in os.environ.items()
            if not k.startswith(("JAX_", "XLA_"))}
    return subprocess.run([sys.executable] + args, env={**base, **env},
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)


def test_entry_point_refuses_a_machine_without_a_tpu():
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode not in (0, None), out.stdout[-500:]
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_import_touches_no_backend_and_cache_is_placed_from_outside(tmp_path):
    """One process per chip: launcher/elastic parents import the package
    and their children need the device, so importing it (and every module
    the smoke uses) must initialise no JAX backend.  Same process: the
    compile-cache helper obeys ``JAX_COMPILATION_CACHE_DIR`` and otherwise
    uses one fixed directory inside the checkout."""
    code = f"""
import os, jax
import deepspeed_tpu
import deepspeed_tpu.launcher.launch, deepspeed_tpu.launcher.runner
import deepspeed_tpu.elasticity.elastic_agent
import deepspeed_tpu.inference.serving, deepspeed_tpu.serving
import deepspeed_tpu.ops.decode_attention, deepspeed_tpu.ops.flash_attention
import deepspeed_tpu.ops.quantized_matmul, deepspeed_tpu.ops.paged_kv
import deepspeed_tpu.models.opt, deepspeed_tpu.models.gpt2
import deepspeed_tpu.parallel.sequence, deepspeed_tpu.telemetry.flops
import chip_smoke
from deepspeed_tpu.utils.platform import enable_compile_cache
import jax._src.xla_bridge as xb
assert not xb._backends, xb._backends

given = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert enable_compile_cache({ROOT!r}) == given
assert jax.config.jax_compilation_cache_dir == given
del os.environ["JAX_COMPILATION_CACHE_DIR"]
fixed = enable_compile_cache({ROOT!r})
assert fixed == os.path.join({ROOT!r}, ".jax_cache"), fixed
assert jax.config.jax_compilation_cache_dir == fixed
assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
assert not xb._backends, xb._backends
print("clean")
"""
    out = _run(["-c", code], JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "given"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("clean")


def test_unrequested_cpu_fallback_is_an_error(monkeypatch):
    """A machine meant to have a chip on which JAX fell back to the CPU
    must not run "green" on interpreted kernels."""
    import jax

    from deepspeed_tpu.utils import platform

    assert platform.on_tpu() is False           # this suite asked for cpu
    assert platform.interpret_kernels() is True
    monkeypatch.setattr(platform, "_requested_platform", lambda: "")
    with pytest.raises(RuntimeError, match="CPU platform was not requested"):
        platform.on_tpu()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.on_tpu() is True
