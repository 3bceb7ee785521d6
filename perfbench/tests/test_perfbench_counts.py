"""Model FLOPs (each reference's ``flops_per_token``), kernel bytes, peaks,
seeds, traffic and the benchmark's files, on known shapes."""

import json
import re

import jax
import numpy as np
import pytest

from perfbench import counts, peaks, seeds, traffic
from perfbench.spec import HERE, ROOT, load_benchmark, load_cell, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _flops_per_token(reference: str):
    return load_module(HERE / "references" / f"{reference}.py"
                       ).flops_per_token


def test_dense_flops_by_hand():
    arch = {"arch_type": "dense", "num_layers": 2, "d_model": 8,
            "num_heads": 2, "num_kv_heads": 1, "head_dim": 4, "d_ff": 16,
            "vocab_size": 32, "mlp_kind": "swiglu"}
    seq = 5
    proj = 2 * 8 * 4 * (2 * 2 + 2 * 1)
    mlp = 2 * 8 * 16 * 3
    attn = 2 * 2 * 2 * 4 * 3                # causal: (5 + 1) / 2 keys
    fwd = 2 * (proj + mlp + attn) + 2 * 8 * 32
    assert _flops_per_token("dense_transformer")(arch, seq) == 3 * fwd


def test_qwen_cut_is_about_six_n():
    """Matmul FLOPs dominate: forward + backward is ~6 x parameters."""
    config = load_cell("qwen1.5-4b.ring-short").config
    arch = config["arch"]
    d, ff, v = arch["d_model"], arch["d_ff"], arch["vocab_size"]
    matmul_params = arch["num_layers"] * (4 * d * d + 3 * d * ff) + d * v
    per_token = _flops_per_token(config["reference"])(arch, 512)
    assert per_token == 1_259_059_200.0
    assert 6 * matmul_params < per_token < 6.1 * matmul_params


def test_update_mix_bytes():
    assert counts.update_mix_bytes(4, 1000) == 3 * 4 * 1000 * 4 + 16 * 4


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_seeds_beyond_32_bits_differ():
    k = [jax.random.key_data(seeds.root_key(s)).tolist()
         for s in (1, 2**32 + 1, 2**33 + 1)]
    assert len({str(x) for x in k}) == 3
    with pytest.raises(ValueError):
        seeds.root_key(-1)


def test_token_pool_is_seeded_and_rows_differ():
    kw = dict(vocab=64, n_agents=3, batch=2, seq_len=16, h=4, rounds=2,
              alpha=0.3)
    a = np.asarray(traffic.token_pool(seeds.purpose_key(5, "traffic"), **kw))
    b = np.asarray(traffic.token_pool(seeds.purpose_key(5, "traffic"), **kw))
    c = np.asarray(traffic.token_pool(seeds.purpose_key(6, "traffic"), **kw))
    assert a.shape == (2, 4, 3, 2, 16) and a.dtype == np.int32
    assert (a == b).all() and not (a == c).all()
    assert ((a >= 0) & (a < 64)).all()
    rows = a.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == rows.shape[0]
    batches = traffic.round_batches(a)
    assert len(batches) == 2
    assert (np.asarray(batches[1]["positions"])[2, 1, 0] == np.arange(16)
            ).all()


def test_benchmark_files_are_complete():
    bench = load_benchmark()
    assert bench["command"][1] == "perfbench/run.py"
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    layers = {m["moves"] for m in bench["per_layer"]}
    assert layers <= {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in bench["workloads"]:
        cell = load_cell(w["name"], bench)
        assert set(cell.limits) == {"loss_gap", "change_gap", "spread_gap"}
        ref = load_module(HERE / "references"
                          / f"{cell.config['reference']}.py")
        assert callable(ref.loss) and callable(ref.flops_per_token)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
