"""A new architecture's training cell comes from new files alone: a
configuration that states its own fields, the reference it names with that
reference's counting functions, a traffic mix and a limits file, and entries
appended to BENCHMARK.json's lists. Nothing under bench/ is edited: the cell
is made in `tmp_path` and the harness finds it by name."""
import json
import os

import pytest

import counts
import run
from run import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STUB_REFERENCE = '''
def matmul_params_per_token(cfg):
    return 1000 * cfg["n_layers"]


def attention_flops_per_token(cfg, context):
    return 10.0 * context


def moe_layers(cfg):
    """The first `first_k_dense` layers run a dense FFN."""
    return cfg["n_layers"] - cfg["first_k_dense"]
'''


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def _stub(tmp_path, body=STUB_REFERENCE):
    path = tmp_path / "stub_reference.py"
    path.write_text(body)
    return dict(_json("configs", "minimind-moe-16e.json"), reference=str(path),
                first_k_dense=1)


def test_counts_come_from_the_named_reference(tmp_path):
    cfg = _stub(tmp_path)
    assert counts.matmul_params_per_token(cfg) == 8000
    assert counts.attention_flops_per_token(cfg, 4.0) == 40.0
    assert counts.moe_layers(cfg) == 7
    assert counts.train_flops_per_token(cfg, 7) == 6.0 * 8000 + 3.0 * 10.0 * 4.0
    assert counts.forward_flops_per_token(cfg, 5.0) == 2.0 * 8000 + 50.0


def test_a_reference_without_its_counts_is_an_error(tmp_path):
    cfg = _stub(tmp_path, STUB_REFERENCE.split("\n\n\ndef moe_layers")[0])
    assert counts.matmul_params_per_token(cfg) == 8000
    with pytest.raises(AttributeError, match="no counting function 'moe_layers'"):
        counts.moe_layers(cfg)


@pytest.mark.parametrize("reader,scopes", [
    ("expert_ffn_roofline.train", {"moe/gemm": 1.0}),
    ("expert_ffn_bwd_roofline.train", {"moe/gemm/bwd/dgrad": 0.4, "moe/gemm/bwd/wgrad": 0.6}),
])
def test_readers_count_the_moe_layers_that_exist(tmp_path, reader, scopes):
    """The same trace read for all 8 layers MoE and for a first layer that
    is dense: the work counted, and so the share, is 7/8 as large."""
    read = load_module(os.path.join(BENCH, "metrics", reader + ".py")).read
    rec = {"trace": {"busy_s": 1.0, "window_s": 1.0, "n_devices": 1, "scope_s": scopes},
           "traffic": _json("traffic", "train_8k.json"), "device_kind": "TPU v5 lite",
           "steps_traced": 3}
    every = read(dict(rec, config=_json("configs", "minimind-moe-16e.json")))
    first_dense = read(dict(rec, config=_stub(tmp_path)))
    assert first_dense == pytest.approx(every * 7 / 8)


def test_a_new_cell_from_new_files_alone(tmp_path):
    from kinds.train import Trainer

    # the files a cell brings: a configuration that states fields over a
    # reference, a traffic mix, a limits file
    cfg = dict(_json("configs", "minimind-moe-16e.json"), name="minimind-moe-16e-window",
               fields={"attn_pattern": ["local"], "window_size": 1024})
    _write(str(tmp_path / "bench/configs/minimind-moe-16e-window.json"), cfg)
    mix = dict(_json("traffic", "train_8k.json"), seq_len=4096, batch=4)
    _write(str(tmp_path / "bench/traffic/train_4k.json"), mix)
    _write(str(tmp_path / "bench/limits/train-16e-window.json"), _json("limits", "train-16e.json"))
    # and entries appended to BENCHMARK.json's lists
    bench = _json(os.pardir, "BENCHMARK.json")
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": "bench/configs/minimind-moe-16e-window.json",
                             "reduced": [], "why": "windowed attention over minimind"})
    bench["workloads"].append({"name": "train-16e-window", "config": cfg["name"],
                               "traffic": "train_4k", "chips": 1, "why": "a test cell"})
    appended = {"train_tokens_per_s", "mfu.train", "expert_ffn_roofline.train"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in appended:
            m["workloads"].append("train-16e-window")
    _write(str(tmp_path / "BENCHMARK.json"), bench)

    spec = run.load_spec("train-16e-window", root=str(tmp_path))
    assert [m["name"] for m in spec.end_to_end] == ["train_tokens_per_s", "setup_s"]
    assert {m["name"] for m in spec.per_layer} == appended - {"train_tokens_per_s"}
    assert spec.config["fields"]["window_size"] == 1024 and spec.traffic["seq_len"] == 4096

    tr = Trainer(spec)  # builds the model and checks its layout against the reference's
    assert tr.model.cfg.attn_pattern == ("local",) and tr.model.cfg.window_size == 1024
    assert tr.model.cfg.remat == "block" and tr.model.cfg.routing.use_kernel
    assert counts.train_flops_per_token(spec.config, mix["seq_len"]) == (
        6.0 * counts.matmul_params_per_token(spec.config)
        + 3.0 * counts.attention_flops_per_token(spec.config, (4096 + 1) / 2))
