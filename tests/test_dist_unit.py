"""Unit tests for the repro.dist layer beyond the subprocess
integration tests: spec sanitation edge cases, no_dist invariants, and
make_dist axis-role derivation (all on the single default device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.context import make_dist, no_dist
from repro.dist.sharding import sanitize_spec, sanitize_specs, tree_shardings
from repro.launch.mesh import auto_mesh


class FakeMesh:
    """Duck-typed mesh for sanitize_spec: only ``.shape`` is consulted,
    so axis sizes > 1 can be exercised without multiple devices."""

    def __init__(self, **shape):
        self.shape = shape


@pytest.fixture(scope="module")
def mesh():
    # 1x1 mesh: axis *names* drive sanitation, sizes are all 1
    return auto_mesh((1, 1), ("data", "model"))


def test_sanitize_drops_axis_missing_from_mesh(mesh):
    got = sanitize_spec(P("data", "pod"), (8, 8), mesh)
    assert got == P("data", None)


def test_sanitize_drops_non_divisible_entry():
    fm = FakeMesh(data=2, model=3)
    assert sanitize_spec(P("data", "model"), (8, 8), fm) == P("data", None)
    assert sanitize_spec(P("model"), (9,), fm) == P("model")
    assert sanitize_spec(P("data"), (7,), fm) == P(None)


def test_sanitize_tuple_entry_drops_innermost_first():
    fm = FakeMesh(data=2, model=3)
    # 12 % (2*3) == 0: both kept
    assert sanitize_spec(P(("data", "model")), (12,), fm) \
        == P(("data", "model"))
    # 8 % 6 != 0 but 8 % 2 == 0: innermost ('model') dropped first
    assert sanitize_spec(P(("data", "model")), (8,), fm) == P("data")
    # unknown axis inside a tuple entry is filtered out
    assert sanitize_spec(P(("data", "pod"), None), (4, 4), fm) \
        == P("data", None)


def test_sanitize_pads_and_truncates_rank(mesh):
    assert sanitize_spec(P("data"), (4, 4, 4), mesh) == P("data", None, None)
    assert sanitize_spec(P("data", None, "model"), (4,), mesh) == P("data")
    assert sanitize_spec(P(), (), mesh) == P()


def test_sanitize_specs_tree(mesh):
    tree = {"a": jax.ShapeDtypeStruct((4, 4), jnp.float32),
            "b": jax.ShapeDtypeStruct((2,), jnp.float32)}
    specs = {"a": P("data", "pod"), "b": P(None)}
    got = sanitize_specs(tree, specs, mesh)
    assert got == {"a": P("data", None), "b": P(None)}


def test_tree_shardings_builds_named_shardings(mesh):
    dist = make_dist(mesh)
    tree = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = {"w": P("data", "model"), "step": P()}
    sh = tree_shardings(dist, tree, specs)
    assert isinstance(sh["w"], NamedSharding)
    assert sh["w"].spec == P("data", "model")
    assert sh["step"].spec == P()


def test_tree_shardings_inactive_is_none():
    assert tree_shardings(no_dist(), {"w": jnp.zeros(2)}, {"w": P()}) is None


def test_no_dist_invariants():
    d = no_dist()
    assert d.active is False
    assert d.mesh is None
    assert d.dp_axes == () and d.ep_axes == () and d.model_axis is None
    assert d.dp_size == d.model_size == d.ep_size == 1
    assert not (d.fsdp or d.zero1 or d.seq_parallel or d.ep_over_dp)
    assert d.sharding(P("data")) is None
    x = jnp.arange(6.0).reshape(2, 3)
    y = d.constrain(x, P("data", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_make_dist_axis_roles(mesh):
    d = make_dist(mesh)
    assert d.active and d.mesh is mesh
    assert d.dp_axes == ("data",)
    assert d.model_axis == "model"
    assert d.ep_axes == ("model",)
    assert d.fsdp and not (d.zero1 or d.seq_parallel or d.ep_over_dp)
    assert d.dp_size == d.model_size == d.ep_size == 1


def test_make_dist_ep_over_dp(mesh):
    d = make_dist(mesh, ep_over_dp=True, fsdp=False, zero1=True)
    assert d.ep_axes == ("data", "model")
    assert d.zero1 and not d.fsdp


def test_make_dist_pure_dp_mesh():
    mesh = auto_mesh((1,), ("data",))
    d = make_dist(mesh)
    assert d.model_axis is None and d.ep_axes == ()
    assert d.ep_size == 1 and d.model_size == 1


def test_constrain_sanitizes_against_shape(mesh):
    d = make_dist(mesh)
    x = jnp.zeros((5, 3))
    # 'pod' unknown + full spec longer than needed: must not raise
    y = d.constrain(x, P(("data", "pod"), "model"))
    assert y.shape == x.shape
