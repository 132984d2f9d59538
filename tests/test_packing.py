"""Bit-packing: exact roundtrip, property-based over shapes/bits."""
import numpy as np
import pytest
from _hyp import given, settings, st

import jax.numpy as jnp

from repro.core.packing import pack, unpack, packed_width, codes_per_byte


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_roundtrip_exact(bits, rng):
    c = rng.integers(0, 2 ** bits, size=(3, 7, 64))
    out = unpack(pack(jnp.asarray(c), bits), bits)
    np.testing.assert_array_equal(np.asarray(out), c)


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_packed_width(bits):
    assert packed_width(64, bits) == 64 * bits // 8
    with pytest.raises(ValueError):
        packed_width(3, bits) if bits != 8 else (_ for _ in ()).throw(ValueError)


@settings(max_examples=40, deadline=None)
@given(
    bits=st.sampled_from([1, 2, 4, 8]),
    lead=st.integers(1, 5),
    blocks=st.integers(1, 8),
    seed=st.integers(0, 2 ** 31),
)
def test_roundtrip_property(bits, lead, blocks, seed):
    r = np.random.default_rng(seed)
    n = blocks * codes_per_byte(bits)
    c = r.integers(0, 2 ** bits, size=(lead, n))
    packed = pack(jnp.asarray(c), bits)
    assert packed.shape == (lead, n * bits // 8)
    assert packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack(packed, bits)), c)


def test_bad_bits():
    with pytest.raises(ValueError):
        pack(jnp.zeros((4, 8), jnp.uint8), 3)


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_strided_byte_layout(bits, rng):
    # byte j holds channels j, j + Wb, j + 2*Wb, ... (i-th in bits [i*b, (i+1)*b))
    # — the layout the Pallas kernels unpack with shifts and a lane concat
    cpb = codes_per_byte(bits)
    c = rng.integers(0, 2 ** bits, size=(5, 64))
    wb = 64 // cpb
    want = sum(c[:, i * wb:(i + 1) * wb] << (i * bits) for i in range(cpb))
    np.testing.assert_array_equal(np.asarray(pack(jnp.asarray(c), bits)), want)
