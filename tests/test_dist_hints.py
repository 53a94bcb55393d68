"""repro.dist.hints on 1 CPU device: identity guarantees + layout stack."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.dist.hints import (_current_mesh, current_layout, layout,
                              mesh_info, shard_hint, suspend_hints)


def test_shard_hint_identity_without_mesh():
    x = jnp.arange(12.0).reshape(3, 4)
    y = shard_hint(x, "dp", "model")
    assert y is x  # exact identity: same object, bit-exact by construction
    z = shard_hint(x, "dp", None)
    assert z is x


def test_shard_hint_rank_mismatch_is_identity():
    x = jnp.ones((2, 3, 4))
    assert shard_hint(x, "dp", None) is x  # 2 tokens for rank 3 → no-op


def test_layout_nesting_restores_previous_mesh():
    assert _current_mesh() is None
    m1 = make_mesh((1, 1), ("data", "model"))
    m2 = make_mesh((1,), ("data",))
    with layout(m1):
        assert _current_mesh() is m1
        assert current_layout() == "tp"
        with layout(m2, "dp_only"):
            assert _current_mesh() is m2
            assert current_layout() == "dp_only"
        assert _current_mesh() is m1
        assert current_layout() == "tp"
    assert _current_mesh() is None
    assert current_layout() == "tp"


def test_layout_by_name_inherits_ambient_mesh():
    m = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(m):
        with layout("dp_only"):
            assert current_layout() == "dp_only"
            assert _current_mesh() is not None
        assert current_layout() == "tp"


def test_layout_restores_on_exception():
    m = make_mesh((1, 1), ("data", "model"))
    try:
        with layout(m):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert _current_mesh() is None


def test_mesh_info_without_mesh():
    dp, msz = mesh_info()
    assert dp == ("data",)
    assert msz == 1


def test_mesh_info_tp_vs_dp_only():
    m = make_mesh((1, 1), ("data", "model"))
    with layout(m):
        dp, msz = mesh_info()
        assert dp == ("data",)
        assert msz == 1  # model axis has extent 1 on this mesh
    with layout(m, "dp_only"):
        dp, msz = mesh_info()
        assert dp == ("data", "model")


def test_shard_hint_values_unchanged_under_mesh():
    m = make_mesh((1, 1), ("data", "model"))
    x = jnp.arange(8.0).reshape(2, 4)
    with layout(m):
        y = shard_hint(x, "dp", "model")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        with suspend_hints():
            assert shard_hint(x, "dp", "model") is x
