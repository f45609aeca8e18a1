"""Tiny stand-ins of the benchmark's configurations and cells, for the
CPU: the published configurations with every size cut down."""
import json
import os

from harness import spec

SHRINK = {
    "xlstm": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                  head_dim=16, vocab_size=256),
}
FILES = {"xlstm": "xlstm-125m"}
CELLS = {"xlstm": "xlstm125m-lrgrid-short"}


def config(family: str) -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           FILES[family] + ".json")) as f:
        cfg = json.load(f)
    cfg.update(SHRINK[family])
    return cfg


def limits(family: str) -> dict:
    """The limits of the real cell of this family."""
    with open(os.path.join(spec.BENCH_DIR, "workloads",
                           CELLS[family] + ".json")) as f:
        return json.load(f)["correct"]["limits"]


def cell(family: str, *, jobs=(1e-3, 5e-4), repeats=2, batch=4, seq=32,
         steps=8, metrics=("sweep_tokens_per_s", "setup_s")) -> spec.Cell:
    traffic = {
        "groups": [{"lr": list(jobs), "repeats": repeats, "batch": batch,
                    "seq": seq, "steps": steps}],
        "session": {"restart_cost_s": 0.5, "introspect_every_s": 600.0,
                    "profile": {"mode": "empirical"}},
        "correct": {"jobs": 2, "limits": limits(family)}}
    ms = [spec.Metric(n, "u", "end_to_end", spec._reader(n))
          for n in metrics]
    return spec.Cell("tiny-" + family, 1, config(family), traffic, ms, [])
