"""Every cell resolves to its files; peaks refuse an unknown chip."""
import json

import pytest

from bench import peaks, spec
from bench import weights as W


def test_every_cell_resolves_to_a_configuration_and_a_mix():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve_cell(bench, w["name"])
        assert cell.config["reference"]
        spec.load_module("references", cell.config["reference"])
        assert cell.traffic["loop"] in ("open", "closed")
        assert set(cell.traffic["engine"]) >= {"n_slots", "max_len",
                                               "page_size", "prefill_chunk"}
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_metric_has_a_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_module("metrics", m["name"]).read)


def test_configuration_files_are_their_entries():
    bench = spec.load_benchmark()
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key not in cfg, f"{key} is cut, yet in {c['file']}"


def test_configurations_keep_the_published_widths():
    from repro.configs.base import get_config

    pub = get_config("minicpm_2b")
    for c in spec.load_benchmark()["configs"]:
        m = spec.model_config(json.loads((spec.ROOT / c["file"]).read_text()))
        assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.d_ff,
                m.vocab_size, m.hd, m.tie_embeddings) == \
            (pub.n_layers, pub.d_model, pub.n_heads, pub.n_kv_heads,
             pub.d_ff, pub.vocab_size, pub.hd, pub.tie_embeddings)


def test_unknown_device_kind_is_an_error():
    assert peaks.peak_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks.peak_for("TPU v9 imaginary")


def test_a_layer_drawn_alone_equals_the_stacked_tree():
    import numpy as np

    cfg = {"num_hidden_layers": 3, "hidden_size": 32,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "intermediate_size": 64, "vocab_size": 300,
           "rms_norm_eps": 1e-5, "rope_theta": 10000.0}
    sizes = W.Sizes.of(cfg)
    seed = 3_000_000_000
    tree = W.make_params(sizes, seed, "bfloat16")
    one = W.layer_weights(sizes, seed, 2, "bfloat16")
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["ffn"]["w2"][2], np.float32),
        np.asarray(one["w2"]))
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["ln1"]["scale"][2], np.float32),
        np.asarray(one["ln1"]))
    emb = np.asarray(W.embed_weights(sizes, seed, "bfloat16"))
    np.testing.assert_array_equal(
        np.asarray(tree["embed"], np.float32), emb)
    assert not emb[sizes.vocab:].any() and emb[:sizes.vocab].std() > 0.01
    other = W.make_params(sizes, seed + 1, "bfloat16")
    assert not np.array_equal(np.asarray(other["embed"], np.float32), emb)


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "minicpm_2b-exact.batch", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=spec.ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_control_without_a_tpu_fails_and_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "control.py"), "--workload",
         "minicpm_2b-exact.chat", "--seeds", "3000000001", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr
