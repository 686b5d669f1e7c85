"""Wire-format tests: bit-packing round-trips and gather-path agreement.

``pack_bits``/``unpack_bits`` are the dense wire format (1 bit per neuron per
cycle); the gather helpers must produce identical results whether or not the
wire is packed, for any neuron count -- including ones that don't divide by 8.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("n", [1, 7, 8, 9, 13, 16, 100, 255, 256, 257])
def test_pack_unpack_roundtrip(n):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.integers(0, 2, n), jnp.int8)
    p = comm.pack_bits(x)
    assert p.shape[-1] == (n + 7) // 8
    assert p.dtype == jnp.uint8
    out = comm.unpack_bits(p, n)
    assert out.dtype == jnp.int8
    assert np.array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.parametrize("shape", [(3, 5), (2, 4, 11), (1, 9)])
def test_pack_unpack_roundtrip_batched(shape):
    rng = np.random.default_rng(sum(shape))
    x = jnp.asarray(rng.integers(0, 2, shape), jnp.int8)
    out = comm.unpack_bits(comm.pack_bits(x), shape[-1])
    assert out.shape == x.shape
    assert np.array_equal(np.asarray(out), np.asarray(x))


def test_pack_bits_wire_bytes():
    """Packing must actually deliver the 8x byte saving it claims."""
    x = jnp.ones((4, 64), jnp.int8)
    assert comm.pack_bits(x).size * 8 == x.size


def _run(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_gather_paths_packed_vs_unpacked_agree():
    """gather_area / gather_global / gather_full give identical results with
    packed=True and packed=False -- including a per-shard width (24) that is
    a multiple of 8 but whose unpadded halves exercise the reshape path, and
    a width (4) below one packed byte."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import comm

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))

        def check(n_loc):
            A_loc, D = 2, 3
            rng = np.random.default_rng(n_loc)

            def body_area(s):
                a = comm.gather_area(s, subgroup_axis="model", packed=True)
                b = comm.gather_area(s, subgroup_axis="model", packed=False)
                return a, b

            def body_global(blk):
                a = comm.gather_global(blk, area_axes=("pod", "data"),
                                       subgroup_axis="model", packed=True)
                b = comm.gather_global(blk, area_axes=("pod", "data"),
                                       subgroup_axis="model", packed=False)
                return a, b

            def body_full(s):
                a = comm.gather_full(s, ("pod", "data", "model"), packed=True)
                b = comm.gather_full(s, ("pod", "data", "model"), packed=False)
                return a, b

            spk = jnp.asarray(
                rng.integers(0, 2, (A_loc * 4, 2 * n_loc)), jnp.int8)
            fa = jax.shard_map(body_area, mesh=mesh,
                           in_specs=P(("pod", "data"), "model"),
                           out_specs=(P(("pod", "data"), None),
                                      P(("pod", "data"), None)),
                           check_vma=False)
            a, b = fa(spk)
            assert np.array_equal(np.asarray(a), np.asarray(b)), "area"

            blk = jnp.asarray(
                rng.integers(0, 2, (D, A_loc * 4, 2 * n_loc)), jnp.int8)
            fg = jax.shard_map(body_global, mesh=mesh,
                           in_specs=P(None, ("pod", "data"), "model"),
                           out_specs=(P(None, None, None),
                                      P(None, None, None)),
                           check_vma=False)
            a, b = fg(blk)
            assert np.array_equal(np.asarray(a), np.asarray(b)), "global"

            spk2 = jnp.asarray(
                rng.integers(0, 2, (A_loc, 8 * n_loc)), jnp.int8)
            ff = jax.shard_map(body_full, mesh=mesh,
                           in_specs=P(None, ("pod", "data", "model")),
                           out_specs=(P(None, None), P(None, None)),
                           check_vma=False)
            a, b = ff(spk2)
            assert np.array_equal(np.asarray(a), np.asarray(b)), "full"

        check(24)
        check(4)
        print("OK")
    """))
