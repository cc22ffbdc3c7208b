"""Counts of ``flops.py`` against hand counts and the program's shapes."""
import json
import pathlib

import pytest

from benchmarks.chip import flops as F

HERE = pathlib.Path(__file__).resolve().parent


def _model(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


# hand counts: bert-large, tied head, vocab padded to 30720, 512 positions
#   30720*1024 + 512*1024 + 2*1024
#   + 24 * (2*2*1024 + 4*1024^2 + 3*1024 + 2*1024*4096 + 4096 + 1024)
# gpt2: vocab padded to 50432, 1024 positions, 12 layers of width 768
@pytest.mark.parametrize("name,params", [("bert-large", 334_268_416),
                                         ("gpt2", 124_564_992)])
def test_param_count_by_hand(name, params):
    assert F.param_count(_model(name)) == params


@pytest.mark.parametrize("name", ["bert-large", "gpt2"])
def test_param_count_matches_program_template(name):
    import math
    from benchmarks.chip import run as RUN
    from repro.models import transformer as T
    from repro.models.layers import is_pd
    import jax
    tmpl = T.model_template(RUN.model_config(_model(name)))
    total = sum(math.prod(pd.shape)
                for pd in jax.tree.leaves(tmpl, is_leaf=is_pd))
    assert F.param_count(_model(name)) == total


# 6 x (24 x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 30522)
#   + 12 x 24 x 1024 x 128 ;  gpt2 likewise at 12 x 768, 50257, S = 1024
@pytest.mark.parametrize("name,seq,flops", [
    ("bert-large", 128, 2_037_215_232),
    ("gpt2", 1024, 854_438_400)])
def test_model_flops_per_token_by_hand(name, seq, flops):
    assert F.model_flops_per_token(_model(name), seq) == flops


@pytest.mark.parametrize("kind,n,per_param", [
    ("local", 1, 32), ("local+var", 1, 36), ("sync", 1, 52),
    ("sync+var", 1, 56), ("sync", 4, 46), ("sync+var", 4, 50)])
def test_optimizer_bytes_by_hand(kind, n, per_param):
    assert F.optimizer_bytes_per_param(kind, n) == per_param
    assert F.optimizer_bytes(10, n, kind) == 10 * per_param


def test_step_kind_names():
    assert F.step_kind(True, False) == "sync"
    assert F.step_kind(False, False) == "local"
    assert F.step_kind(True, True) == "sync+var"
    with pytest.raises(ValueError):
        F.optimizer_bytes_per_param("both", 1)
