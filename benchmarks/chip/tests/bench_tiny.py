"""A tiny cell for the benchmark's tests, added to a scratch checkout as
files and entries only: a configuration, a traffic mix and limits."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[3]

TINY = {
    "name": "tiny", "family": "dense_lm", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "vocab_size": 512, "qkv_bias": True, "compute_dtype": "bfloat16",
    "param_dtype": "float32",
}
TINY_TRAFFIC = {
    "name": "tiny", "kind": "train", "loop": "closed", "batch": 4, "seq": 64,
    "pattern": "uniform", "mesh": {"data": 1, "model": 1},
    "strategy": "2d_finalized", "optimizer": {"name": "adafactor", "lr": 0.01},
    "model": {"xent_chunk": 0, "attn_chunk": 32}, "first_steps": 3,
}
# set as the cells' limits are (compare.py): above the program's readings
# on the tiny cell (loss 2.6e-4, grad 4.5e-3, update 2.6e-2 at most over
# three seeds, CPU), below the fp8 control's (1.9e-3, 2.5e-2, 1.3e-2 at
# least) where that is three times the program's
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.012,
               "update_norm_gap": 0.08}


def make_checkout(dest: pathlib.Path, config=TINY, traffic=TINY_TRAFFIC,
                  limits=TINY_LIMITS, chips=1) -> pathlib.Path:
    """A checkout holding the benchmark's code and one tiny cell, added as
    files and entries only: a configuration, a traffic mix, limits."""
    chip = dest / "benchmarks" / "chip"
    src = ROOT / "benchmarks" / "chip"
    for d in ("families", "metrics"):
        shutil.copytree(src / d, chip / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(src / "peaks.json", chip / "peaks.json")
    for d in ("configs", "traffic", "limits"):
        (chip / d).mkdir(parents=True, exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"{config['name']}.{traffic['name']}"
    (chip / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (chip / "traffic" / f"{traffic['name']}.json").write_text(
        json.dumps(traffic))
    (chip / "limits" / f"{name}.json").write_text(json.dumps(limits))
    bench["configs"].append({
        "name": config["name"], "source": "test",
        "file": f"benchmarks/chip/configs/{config['name']}.json",
        "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": traffic["name"], "chips": chips,
                               "why": "tiny"})
    for m in bench["per_layer"]:
        m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest
