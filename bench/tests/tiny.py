"""A copy of the benchmark with one cell at the `tiny` size, for CPU tests:
the same harness, driver and reference, a tiny config and traffic mix."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "train.tiny.per_layer"

CONFIG = {"source": "test", "arch": "tiny", "family": "dense",
          "reduced": [], "hidden_size": 64, "intermediate_size": 128,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 257,
          "attention_bias": False, "qk_norm": False, "rms_norm_eps": 1e-5,
          "rope_theta": 10000.0, "tie_word_embeddings": False,
          "torch_dtype": "float32"}
# CPU float32 against the float32 reference: the first step agrees to
# rounding; these limits only have to part a sound run from a broken one
LIMITS = {"loss_gap": {"limit": 1e-3}, "clip_gap": {"limit": 0.05},
          "grad_gap": {"limit": 0.2}, "change_gap": {"limit": 0.05}}


def make(root: str) -> str:
    """Write the tiny benchmark under `root`; returns root."""
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".run"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    traffic = os.path.join(REPO, "bench", "traffic",
                           "dp_per_layer_b4_t512.json")
    with open(traffic) as fh:
        tr = json.load(fh)
    # float32 state: no rounding to average out, every leaf compared
    tr.update(seq=32, rows=64, backend="xla", clip_leaf_min=0)
    # every example clipped, so that a wrong clip factor shows
    tr["dp"] = dict(tr["dp"], init_threshold=0.01)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": CELL, "config": "tiny", "traffic": "tiny_train",
         "chips": 1, "why": "test"}]

    def metric(name, unit, cell, **kw):
        return dict(name=name, unit=unit, better="lower",
                    source="host_clock", workloads=[cell], **kw)

    bench["end_to_end"] = [
        metric("train_tokens_per_s", "tokens/s", CELL, bound=0.02),
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    readers = sorted(f[:-3] for f in os.listdir(
        os.path.join(REPO, "bench", "metrics")) if f.endswith(".py"))
    bench["per_layer"] = [metric(r, "1", CELL, layer="test", moves="setup_s")
                          for r in readers]
    files = {"BENCHMARK.json": bench, "bench/configs/tiny.json": CONFIG,
             "bench/traffic/tiny_train.json": tr,
             f"bench/limits/{CELL}.json": LIMITS}
    for path, doc in files.items():
        with open(os.path.join(root, path), "w") as fh:
            json.dump(doc, fh)
    return root
