"""OpRegistry: registration, binding/swap, refusal, freeze semantics."""

import pytest

from repro.core.abi import AbiIncompatibility, AbiString
from repro.core.platform import CLUSTER, LAPTOP, TPU_V5E, chip_platform
from repro.core.registry import ImplKind, OpImpl, OpRegistry

V5E = chip_platform(TPU_V5E, 1)   # declares pallas_kernels


def _abi(name="op", sig="s", minor=0):
    return AbiString.make(name, sig, minor=minor)


def _reg_with_op():
    reg = OpRegistry()
    reg.register(OpImpl(abi=_abi(), kind=ImplKind.REFERENCE, fn=lambda: "ref",
                        provider="jnp"))
    reg.register(OpImpl(abi=_abi(minor=1), kind=ImplKind.NATIVE, fn=lambda: "native",
                        requires_feature="pallas_kernels", provider="pallas"))
    return reg


def test_swap_on_capable_platform():
    reg = _reg_with_op()
    binding = reg.bind(["op"], V5E, native=True, freeze=False)
    assert binding["op"]() == "native"
    assert binding.reports[0].swapped


def test_no_swap_when_disabled():
    reg = _reg_with_op()
    binding = reg.bind(["op"], V5E, native=False, freeze=False)
    assert binding["op"]() == "ref"
    assert not binding.reports[0].swapped


def test_no_swap_without_feature():
    """Shifter on a host without the vendor stack keeps the container lib."""
    reg = _reg_with_op()
    binding = reg.bind(["op"], LAPTOP, native=True, freeze=False)
    assert binding["op"]() == "ref"
    assert "pallas_kernels" in binding.reports[0].reason


def test_abi_refusal_keeps_reference():
    reg = OpRegistry()
    reg.register(OpImpl(abi=_abi(sig="s1"), kind=ImplKind.REFERENCE, fn=lambda: "ref"))
    # incompatible native: registered permissively, must NOT be swapped in
    ok = reg.register(
        OpImpl(abi=_abi(sig="s2"), kind=ImplKind.NATIVE, fn=lambda: "bad"),
        strict=False,
    )
    assert not ok
    binding = reg.bind(["op"], V5E, native=True, freeze=False)
    assert binding["op"]() == "ref"


def test_strict_registration_raises():
    reg = OpRegistry()
    reg.register(OpImpl(abi=_abi(sig="s1"), kind=ImplKind.REFERENCE, fn=lambda: 0))
    with pytest.raises(AbiIncompatibility):
        reg.register(OpImpl(abi=_abi(sig="s2"), kind=ImplKind.NATIVE, fn=lambda: 0))


def test_native_first_requires_reference():
    reg = OpRegistry()
    with pytest.raises(KeyError):
        reg.register(OpImpl(abi=_abi(), kind=ImplKind.NATIVE, fn=lambda: 0))


def test_freeze_blocks_registration():
    reg = _reg_with_op()
    reg.bind(["op"], CLUSTER, native=False, freeze=True)
    with pytest.raises(RuntimeError):
        reg.register(OpImpl(abi=_abi("op2"), kind=ImplKind.REFERENCE, fn=lambda: 0))
    reg.thaw()
    reg.register(OpImpl(abi=_abi("op2"), kind=ImplKind.REFERENCE, fn=lambda: 0))


def test_binding_reports_describe():
    reg = _reg_with_op()
    binding = reg.bind(["op"], V5E, native=True, freeze=False)
    assert "op" in binding.describe()


def test_unknown_op():
    reg = _reg_with_op()
    with pytest.raises(KeyError):
        reg.bind(["nope"], LAPTOP, native=False, freeze=False)
