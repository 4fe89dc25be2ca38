"""Mesh builder tests — graceful degradation on hosts with fewer devices
than the requested shape.  Written against whatever device count the
process actually has (1 in the plain tier-1 run, 8 in the forced
multi-device CI job): degradation is provoked by requesting more devices
than exist, never by assuming a specific count."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.launch import mesh as lm

AVAIL = len(jax.devices())


# ------------------------------------------------------------ fit_shape --
def test_fit_shape_prefers_later_axes():
    """Later (model/TP) axes keep their extent first; leading DP axes
    give way."""
    assert lm.fit_shape((2, 4), 8) == (2, 4)
    assert lm.fit_shape((2, 4), 4) == (1, 4)
    assert lm.fit_shape((2, 4), 2) == (1, 2)
    assert lm.fit_shape((2, 4), 1) == (1, 1)
    assert lm.fit_shape((2, 16, 16), 16) == (1, 1, 16)
    assert lm.fit_shape((4,), 3) == (3,)


# ------------------------------------------- builders, degradation path --
def test_host_mesh_degrades_with_warning():
    """Request double the available devices on the model axis: the mesh
    must shrink to what exists, model axis first."""
    with pytest.warns(UserWarning, match="degrading"):
        mesh = lm.make_host_mesh((2, 2 * AVAIL))
    assert dict(mesh.shape) == {"data": 1, "model": AVAIL}
    assert mesh.size == AVAIL


def test_host_mesh_exact_fit_stays_silent():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mesh = lm.make_host_mesh((1, AVAIL))
    assert mesh.size == AVAIL


def test_production_mesh_degrades_to_available():
    with pytest.warns(UserWarning):       # (16, 16) never fits in CI
        mesh = lm.make_production_mesh()
    assert mesh.size == AVAIL


def test_degradation_emits_trace_marker():
    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        with pytest.warns(UserWarning):
            lm.make_host_mesh((2, 2 * AVAIL))
    finally:
        tracer.disable()
    marks = [e for e in tracer.events if e["name"] == "mesh.degraded"]
    assert len(marks) == 1
    assert marks[0]["args"]["requested"] == [2, 2 * AVAIL]
    assert marks[0]["args"]["got"] == [1, AVAIL]
    assert marks[0]["args"]["devices"] == AVAIL


# ----------------------------------------------------------- data mesh ----
def test_data_mesh_int_degrades_with_warning():
    """Asking for more devices than are visible no longer degrades: a
    serving mesh never runs on fewer devices than asked, so it raises."""
    with pytest.raises(ValueError, match="visible"):
        lm.make_data_mesh(2 * AVAIL)
    mesh = lm.make_data_mesh(AVAIL)
    assert mesh.size == AVAIL
    assert tuple(mesh.axis_names) == ("data",)


def test_data_mesh_default_and_explicit():
    assert lm.make_data_mesh().size == AVAIL
    mesh = lm.make_data_mesh(jax.devices())
    assert tuple(mesh.axis_names) == ("data",)
    assert lm.as_data_mesh(mesh) is mesh


def test_data_mesh_int_exact_stays_silent():
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mesh = lm.make_data_mesh(1)
    assert mesh.size == 1


def test_as_data_mesh_rejects_wrong_axes():
    grid = np.asarray(jax.devices()).reshape(1, AVAIL)
    wrong = jax.sharding.Mesh(grid, ("data", "model"))
    with pytest.raises(AssertionError, match="1-D"):
        lm.as_data_mesh(wrong)
    with pytest.raises(AssertionError, match="Mesh"):
        lm.as_data_mesh(jax.devices())
