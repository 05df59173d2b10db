"""Meshes: Auto axes on every builder, host meshes over every local device."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import AxisType

from repro.launch.mesh import make_host_mesh

SRC = Path(__file__).resolve().parent.parent / "src"


class TestHostMesh:
    def test_axes_are_auto(self):
        mesh = make_host_mesh()
        assert mesh.axis_names == ("data", "model")
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)

    def test_spans_every_device(self):
        mesh = make_host_mesh()
        assert mesh.devices.size == jax.device_count()
        assert dict(mesh.shape) == {"data": jax.device_count(), "model": 1}

    @pytest.mark.parametrize("shape", [(2, 1), (1, 2), (0, 1)])
    def test_shape_that_does_not_cover_the_devices_raises(self, shape):
        with pytest.raises(ValueError, match="needs"):
            make_host_mesh(shape)

    def test_logical_constraint_traces_under_the_mesh(self):
        """shard() constraints name mesh axes: they need Auto axes."""
        from repro.models.common import shard
        from repro.parallel.sharding import use_rules

        mesh = make_host_mesh()

        def f(x):
            with use_rules({"batch": "data", "ff": "model"}, mesh):
                return shard(x * 2, "batch", "ff")

        with mesh:
            out = jax.jit(f)(jax.numpy.ones((4, 8)))
        assert float(out.sum()) == 64.0


_MANY_DEVICES = textwrap.dedent("""
    import json, jax
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh, make_production_mesh

    out = {}
    for name, mesh in (("pod", make_production_mesh()),
                       ("multi_pod", make_production_mesh(multi_pod=True))):
        out[name] = [mesh.devices.shape, [t == AxisType.Auto for t in mesh.axis_types]]
    host = make_host_mesh()
    out["host_default"] = [host.devices.shape, host.devices.size == jax.device_count()]
    four = make_host_mesh((2, 2), devices=jax.devices()[:4])
    out["host_2x2_of_4"] = [four.devices.shape, [t == AxisType.Auto for t in four.axis_types]]
    try:
        make_host_mesh((2, 2))
        out["host_2x2_of_all"] = "built"
    except ValueError:
        out["host_2x2_of_all"] = "raised"
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def many_devices():
    """The builders run in a child with 512 virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=512")
    res = subprocess.run([sys.executable, "-c", _MANY_DEVICES], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


class TestManyDevices:
    @pytest.mark.parametrize("name,shape", [("pod", [16, 16]), ("multi_pod", [2, 16, 16])])
    def test_production_mesh_axes_are_auto(self, many_devices, name, shape):
        got_shape, auto = many_devices[name]
        assert got_shape == shape
        assert all(auto)

    def test_host_mesh_spans_all_local_devices(self, many_devices):
        assert many_devices["host_default"] == [[512, 1], True]

    def test_host_mesh_on_a_subset(self, many_devices):
        assert many_devices["host_2x2_of_4"] == [[2, 2], [True, True]]

    def test_host_mesh_shape_must_cover_all_devices(self, many_devices):
        assert many_devices["host_2x2_of_all"] == "raised"
