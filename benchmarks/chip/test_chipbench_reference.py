"""CPU tests of each configuration's plain reference, at a size a test run
can hold: the served form of the model computes the published one, the
program serves it as the reference computes it, and the control, one step
below the stated precision, fails the configuration's limit."""
from __future__ import annotations

import pathlib

import jax
import numpy as np
import pytest

import harness

HERE = pathlib.Path(__file__).resolve().parent
SMALL = {"dgcnn": {"n_points": 128, "k": 8}}
SEED = 2**31 + 3


def _setup(name):
    cfg = harness.load_json(HERE / "configs" / f"{name}.json")
    ref = harness.load_module(HERE / cfg["reference"])
    served = harness.load_module(HERE / cfg["served"])
    sizes = dict(cfg["sizes"], **SMALL[name])
    rng = np.random.default_rng(0)
    inputs = {k: rng.standard_normal(
        (2, sizes["n_points"], *spec["shape"][1:])).astype(np.float32)
        for k, spec in cfg["inputs"].items()}
    return cfg, ref, served, sizes, inputs


def _rel(got, want):
    """Relative L2 error of each answer."""
    got, want = np.asarray(got), np.asarray(want)
    n = len(want)
    return (np.linalg.norm((got - want).reshape(n, -1), axis=1)
            / np.linalg.norm(want.reshape(n, -1), axis=1))


def _each(fn, inputs):
    return np.stack([np.asarray(fn(**{k: v[i] for k, v in inputs.items()}))
                     for i in range(2)])


@pytest.mark.parametrize("name", sorted(SMALL))
def test_served_form_computes_the_published_model(name):
    cfg, ref, served, sizes, inputs = _setup(name)
    with jax.default_matmul_precision("highest"):
        fn, _ = served.build(SEED, **sizes)
        params = ref.init_params(SEED, **sizes)
        want = ref.batched(params)(**inputs)
        got = _each(jax.jit(fn), inputs)
        factored = ref.batched(params, factored=True)(**inputs)
    assert np.median(_rel(got, want)) < cfg["check"]["median_rel_err"]
    assert np.median(_rel(factored, want)) < cfg["check"]["median_rel_err"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_serves_what_the_reference_computes(name):
    from repro import gcv
    cfg, ref, served, sizes, inputs = _setup(name)
    with jax.default_matmul_precision("highest"):
        fn, example = served.build(SEED, **sizes)
        model = gcv.compile(fn, example)
        got = _each(lambda **x: model(**x)[0], inputs)   # its one output
        want = ref.batched(ref.init_params(SEED, **sizes))(**inputs)
    assert np.median(_rel(got, want)) < cfg["check"]["median_rel_err"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails_the_limit(name):
    cfg, ref, _, sizes, inputs = _setup(name)
    params = ref.init_params(SEED, **sizes)
    with jax.default_matmul_precision("highest"):
        want = ref.batched(params)(**inputs)
        got = ref.batched(params, precision="high")(**inputs)
    assert np.median(_rel(got, want)) > cfg["check"]["median_rel_err"]
