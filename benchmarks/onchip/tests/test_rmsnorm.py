"""A dense decoder with parametric RMSNorm, rehearsed end to end through
``run.run_cell``: ``data/dense_rmsnorm.json``, a fixture and no cell of
the benchmark, with grouped-query attention and an untied head at the
epsilon the program's RMSNorm applies (1e-6). It proves correct, its fp8
control does not, a program that serves with its scales dropped does not,
and a file whose epsilon the program cannot take is refused before any
run. It is held to the rehearsal limit, as every rehearsal; its readings
are in ``data/dense_rmsnorm.limits.json``."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

import cells
import system
from rehearse import rehearse

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MIXES = ("sum", "gen")


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fixture_cell(mix: str) -> cells.Cell:
    """The fixture under one of the benchmark's mixes, at rehearsal sizes."""
    cell = cells.Cell(f"dense-rmsnorm.{mix}", 1,
                      _read(DATA, "dense_rmsnorm.json"),
                      _read(cells.HERE, "traffic", f"{mix}.json"),
                      _read(DATA, "dense_rmsnorm.limits.json"),
                      _read(cells.CHECKOUT, "BENCHMARK.json"))
    return cells.shrink(cell)


def test_fixture_draws_scales_and_hands_them_to_the_program():
    import weights
    conf = fixture_cell("gen").conf
    arch = cells.arch(conf)
    w = weights.make_weights(arch.shapes(conf), 7)
    for leaf in (w["layers"]["norm1"], w["layers"]["norm2"], w["final_norm"]):
        assert 0.5 <= float(leaf.min()) and float(leaf.max()) <= 1.5
        assert float(leaf.max()) - float(leaf.min()) > 0.5
    params = system.program_params(arch, w, system.model_config(conf, arch))
    assert params["final_norm"]["scale"] is w["final_norm"]
    assert params["blocks"]["pos0"]["norm2"]["scale"] is w["layers"]["norm2"]
    assert "lm_head" in params["embed"]


@pytest.mark.parametrize("mix", MIXES)
def test_fixture_rehearses_correct_and_its_control_does_not(mix):
    out = rehearse(fixture_cell(mix), seed=2**31 + 29, seconds=2.0,
                   control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["info"]["window_compiles"] == 0
    control = out["info"]["control"]
    assert control["correct"] is False, control["checks"]
    assert control["argmax_differs"] > out["info"]["argmax_differs"]


_program_params = system.program_params


def scales_dropped(arch, w, cfg):
    """The program served with every norm scale set to ones."""
    params = _program_params(arch, w, cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.ones_like(a)
        if getattr(path[-1], "key", None) == "scale" else a, params)


@pytest.mark.parametrize("mix", MIXES)
def test_dropped_scales_are_not_correct(monkeypatch, mix):
    monkeypatch.setattr(system, "program_params", scales_dropped)
    out = rehearse(fixture_cell(mix), seed=11, seconds=2.0)
    assert not out["correct"], out["checks"]


def _with_norm_eps(base):
    """``base`` as a ModelConfig that has a ``norm_eps`` field."""
    cls = dataclasses.make_dataclass(
        "WithNormEps", [("norm_eps", float, 1e-6)], bases=(type(base),),
        frozen=True)
    return cls(**{f.name: getattr(base, f.name)
                  for f in dataclasses.fields(base)})


@pytest.mark.parametrize("program", ["as_is", "with_norm_eps"])
@pytest.mark.parametrize("conf_file,eps", [
    ("rmsnorm", 1e-5), ("olmo-1b", 1e-6)])
def test_an_epsilon_the_program_does_not_apply(monkeypatch, program,
                                               conf_file, eps):
    """Refused, naming ``norm_eps``, where the program's ModelConfig has no
    such field; taken where it has one."""
    conf = (_read(DATA, "dense_rmsnorm.json") if conf_file == "rmsnorm"
            else cells.load("olmo-1b.gen").conf)
    arch = cells.arch(conf)
    assert system.model_config(conf, arch)      # as the file states it
    conf = dict(conf, norm=dict(conf["norm"], eps=eps))
    if program == "with_norm_eps":
        base = _with_norm_eps(system.get_arch(conf["program"]["arch"]))
        monkeypatch.setattr(system, "get_arch", lambda name: base)
    base = system.get_arch(conf["program"]["arch"])
    if any(f.name == "norm_eps" for f in dataclasses.fields(base)):
        assert system.model_config(conf, arch).norm_eps == eps
    else:
        with pytest.raises(ValueError, match="norm_eps"):
            system.model_config(conf, arch)
