"""The FLOPs functions and the references' parameter trees against the
system under test's own counts and trees."""
import json
import os

import jax
import pytest

from harness import flops, spec
from harness.bench import model_config
from harness.reference import family, leaf_paths
from models.common import is_leaf


def _config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params", [("xlstm-125m", 332.6e6)])
def test_param_count_matches_the_system(name, params):
    from repro.models.params import param_count
    from repro.models.transformer import model_spec
    cfg = _config(name)
    ours = flops.param_count(cfg)
    assert ours == param_count(model_spec(model_config(cfg)))
    assert abs(ours - params) < 0.05e6


@pytest.mark.parametrize("name", ["xlstm-125m"])
def test_reference_tree_is_the_systems_tree(name):
    from repro.models.params import is_spec
    from repro.models.transformer import model_spec
    cfg = _config(name)
    ref = family(cfg["family"]).spec(cfg)
    prog = model_spec(model_config(cfg))
    assert leaf_paths(ref, is_leaf) == leaf_paths(prog, is_spec)
    shapes = lambda t, f: [x.shape for x in  # noqa: E731
                           jax.tree.leaves(t, is_leaf=f)]
    assert shapes(ref, is_leaf) == shapes(prog, is_spec)


def test_flops_per_token():
    # xlstm-125m: every matmul weight but the input embedding (the
    # untied head and the depthwise convolution among them), and the mLSTM's causal mixing over 1024
    # positions: about 1.99 GFLOP a token
    cfg = _config("xlstm-125m")
    xl = flops.per_token(cfg, 1024)
    up = 2 * 768
    block = 2 * 768 * up + 4 * up + 3 * up * up + 2 * up * 4 + up * 768
    weights = 24 * block + 768 * 50304
    mixing = 24 * 2 * 2 * 4 * (up // 4) * 1024 / 2
    assert xl == pytest.approx(6 * weights + 3 * mixing)
    assert 1.95e9 < xl < 2.03e9


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
