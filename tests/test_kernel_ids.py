"""Every ``pallas_call`` of ops/ carries its kernel identity.

``pallas_call(metadata={"kernel": <id>})`` lowers, for TPU, to
``kernel_metadata`` on the Mosaic custom call: the text a device trace
prints as the event's name, which is how the benchmark's per-kernel metrics
find their events. Lowered here for the TPU platform without a chip (no
compile, so no TPU compiler is loaded: tests/test_chip_compile.py owns
that)."""

import re

import jax
import jax.numpy as jnp
import pytest

from sharetrade_tpu.ops import attention

METADATA = re.compile(r'kernel_metadata = "([^"]*)"')


def kernel_ids(lowered) -> list[str]:
    """The ``kernel`` id of every Mosaic custom call in a TPU lowering, in
    program order (MLIR prints the JSON's quotes as ``\\22``)."""
    assert "tpu_custom_call" in lowered.as_text()
    return [re.search(r'kernel\\22:\\22(\w+)\\22', m).group(1)
            for m in METADATA.findall(lowered.as_text())]


@pytest.fixture
def tpu_backend(monkeypatch):
    """The package asks ``jax.default_backend()`` whether to run its kernels
    compiled or interpreted; answer for the platform lowered for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("shape,window", [
    pytest.param((2, 2, 256, 128), None, id="full_kv"),      # 3 calls
    pytest.param((8, 2, 1225, 128), 202, id="banded"),       # 3 calls
])
def test_attention_kernels_carry_their_ids(tpu_backend, shape, window):
    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, causal=True,
                                        local_window=window, use_pallas=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(
        x, x, x).lower(lowering_platforms=("tpu",))
    ids = kernel_ids(lowered)
    assert sorted(set(ids)) == sorted([attention.KERNEL_FWD,
                                       attention.KERNEL_DQ,
                                       attention.KERNEL_DKDV])
    assert len(ids) == lowered.as_text().count("tpu_custom_call")
    # metadata=, not name=: the custom calls keep the names XLA derives
    # from the name stack (what the accepted attention_roofline matches).
    assert "flash_fwd" not in re.sub(r'kernel_metadata = "[^"]*"', "",
                                     lowered.as_text())

