"""CUDA_VISIBLE_DEVICES-analogue parsing + renumbering semantics."""

import jax
import pytest

pytest.importorskip("hypothesis", reason="property-based tests need hypothesis")
from hypothesis import given, strategies as st  # noqa: E402

from repro.core.env import (
    ENV_PLATFORM,
    native_ops_default,
    parse_visible_devices,
    resolve_platform,
    select_devices,
)
from repro.core.platform import CLUSTER, LAPTOP, TPU_V5E, detect_platform  # noqa: E402


@pytest.mark.parametrize(
    "value,active,indices",
    [
        (None, False, None),
        ("", False, None),
        ("all", True, None),
        ("ALL", True, None),
        ("0", True, (0,)),
        ("0,2", True, (0, 2)),
        (" 1 , 3 ", True, (1, 3)),
        ("0,0", False, None),        # duplicates invalid
        ("-1", False, None),
        ("junk", False, None),
        ("0,junk", False, None),
    ],
)
def test_parse_visible(value, active, indices):
    v = parse_visible_devices(value)
    assert v.active == active
    assert v.indices == indices


def test_renumbering_from_zero():
    """§IV-A.3: visible devices are addressable from logical index 0."""
    devs = list(jax.devices())
    v = parse_visible_devices("0")
    sel = select_devices(v, devs)
    assert sel == [devs[0]]
    # out-of-range physical ids are dropped, order preserved
    v2 = parse_visible_devices("5,0")
    sel2 = select_devices(v2, devs)
    assert sel2 == [devs[0]]


def test_invalid_value_keeps_all_devices():
    devs = list(jax.devices())
    assert select_devices(parse_visible_devices("junk"), devs) == devs


def test_platform_override_and_detection():
    assert resolve_platform({ENV_PLATFORM: "tpu-v5e-1chip"}).name == "tpu-v5e-1chip"
    with pytest.raises(KeyError):
        resolve_platform({ENV_PLATFORM: "nope"})
    assert resolve_platform({}).name == "laptop"  # 1 CPU device


class _FakeDevice:
    """Stands in for a jax.Device: detect_platform reads only these."""

    def __init__(self, platform: str, device_kind: str):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize("n", [1, 4])
def test_detect_platform_is_the_v5e_chips_present(n):
    p = detect_platform([_FakeDevice("tpu", "TPU v5 lite")] * n)
    assert p.hardware is TPU_V5E
    assert p.mesh_shape == (n,) and p.num_devices == n
    assert p.has("pallas_kernels") and not p.has("pallas_interpret")
    assert p.has("native_collectives") == (n > 1)


def test_detect_platform_unknown_tpu_kind_raises():
    with pytest.raises(KeyError, match="TPU v4"):
        detect_platform([_FakeDevice("tpu", "TPU v4")])


def test_detect_platform_cpu_hosts():
    assert detect_platform([_FakeDevice("cpu", "cpu")]) is LAPTOP
    assert detect_platform([_FakeDevice("cpu", "cpu")] * 8) is CLUSTER


def test_native_ops_default():
    assert native_ops_default({"REPRO_NATIVE_OPS": "1"})
    assert not native_ops_default({"REPRO_NATIVE_OPS": "0"})
    assert not native_ops_default({})


@given(st.lists(st.integers(0, 100), min_size=1, max_size=8, unique=True))
def test_valid_lists_always_activate(ids):
    v = parse_visible_devices(",".join(map(str, ids)))
    assert v.active and v.indices == tuple(ids)
