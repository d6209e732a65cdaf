"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide §2, steps 1-2).

chip_smoke.py has no CPU mode: it refuses any platform but ``tpu``. The
rehearsal drives its phase functions here instead, at `LlamaConfig.tiny`
under the suite's forced CPU backend — the same control flow, entry
points and checks the chip run takes, minus the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(vocab_size=256, dim=64, n_heads=4, n_kv_heads=2,
            hidden_dim=128, max_seq_len=256, n_layers=2,
            dtype="bfloat16", param_dtype="bfloat16")


def _tiny_spec():
    return {
        "seed": 0, "platform": "cpu", "num_tpus": 0,
        "serve": {
            "model": dict(TINY),
            "engine": {"num_slots": 4, "max_seq_len": 96,
                       "prefill_buckets": (8, 16), "kv_layout": "paged",
                       "kv_block_size": 4, "num_kv_blocks": 64},
            # short, longer than the top bucket (chunked), shared prefix
            "prompts": [(5, 0), (23, 0), (12, 0), (12, 8)],
            "max_tokens": 4,
        },
        "train": {
            "model": dict(TINY, attn_impl="flash"),
            "steps": 3, "batch_size": 2, "seq_len": 33,
            # off-TPU flash attention is XLA attention: no kernel to find
            "expect_kernel": False,
        },
    }


def test_serve_phase_matches_reference_on_cpu(monkeypatch):
    monkeypatch.delenv("RAY_TPU_FAKE_CHIPS", raising=False)
    spec = _tiny_spec()
    served = chip_smoke.serve_phase(spec)
    spec["serve"]["served_tokens"] = served["tokens"]
    reference = chip_smoke.reference_phase(spec)
    chip_smoke.check_serve(spec, served, reference)
    # On the CPU the engine is token-exact with `generate`.
    assert served["tokens"] == reference["tokens"]
    agreement = chip_smoke.serve_agreement(served, reference)
    assert agreement["equal_generate_given_prefix"] == agreement["tokens"]
    assert served["replica"]["platform"] == "cpu"
    assert served["replica"]["pid"] != os.getpid()
    assert served["tpu_library_holders"] == []
    # check_serve really compares: a token that is not the reference's
    # choice, and no tie either, must fail it.
    reference["forced_argmax"][0][0] ^= 1
    reference["forced_gap"][0][0] = 1.0
    with pytest.raises(AssertionError, match="more than a tie"):
        chip_smoke.check_serve(spec, served, reference)


def test_train_phase_on_cpu():
    spec = _tiny_spec()
    out = chip_smoke.train_phase(spec)
    chip_smoke.check_train(spec, out)
    assert out["device"]["platform"] == "cpu"
    # The kernel check is live: asked for, its absence fails the phase.
    spec["train"]["expect_kernel"] = True
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.check_train(spec, out)


def test_four_chip_phase_on_virtual_devices():
    spec = chip_smoke.four_chip_spec(0)
    spec.update(platform="cpu",
                model=dict(TINY, n_layers=1, attn_impl="flash"),
                batch_size=4, seq_len=33,
                message_bytes=[4 * 128 * 4 * 2],
                collective_impl="pallas_interpret")
    out = chip_smoke.four_chip_phase(spec)
    assert set(out["legs"]) == {"one_device", "sharded", "overlap"}
    assert out["ring_attention"] == "equal"
    assert all(v == "equal" for k, v in out["collectives"].items()
               if not k.startswith("quantized"))


def test_script_refuses_without_a_tpu():
    """`python chip_smoke.py` off-TPU: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for args in ([], ["--four-chips"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "refusing to run" in proc.stderr


def test_default_spec_is_llama3_8b_widths():
    from ray_tpu.models.llama import LlamaConfig

    spec = chip_smoke.chip_spec(0)
    published = LlamaConfig.llama3_8b()
    for phase in ("serve", "train"):
        config = chip_smoke._model_config(spec[phase]["model"])
        for name in chip_smoke.LLAMA3_8B_WIDTHS:
            assert getattr(config, name) == getattr(published, name)
    assert spec["num_tpus"] == 1 and spec["platform"] == "tpu"
    assert json.dumps(spec)  # plain data: the parent never needs JAX
