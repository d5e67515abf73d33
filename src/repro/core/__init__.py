"""repro.core — the paper's contribution: portable, high-performance
program containers for JAX (ABI-verified op substitution, environment-
triggered resource injection, single-blob image distribution)."""

from repro.core.abi import AbiIncompatibility, AbiString, parse_abi, signature_digest
from repro.core.bundle import Bundle, BundleError
from repro.core.env import parse_visible_devices, resolve_platform, select_devices
from repro.core.gateway import Gateway, GatewayError
from repro.core.platform import (
    CLUSTER,
    LAPTOP,
    PLATFORMS,
    TPU_V5E,
    HardwareSpec,
    Platform,
    detect_platform,
)
from repro.core.registry import (
    ImplKind,
    OpBinding,
    OpDecl,
    OpImpl,
    OpRegistry,
    SwapReport,
    global_registry,
)
from repro.core.runtime import Container, DeploymentError, Runtime

__all__ = [
    "AbiIncompatibility", "AbiString", "parse_abi", "signature_digest",
    "Bundle", "BundleError",
    "parse_visible_devices", "resolve_platform", "select_devices",
    "Gateway", "GatewayError",
    "CLUSTER", "LAPTOP", "PLATFORMS", "TPU_V5E",
    "HardwareSpec", "Platform", "detect_platform",
    "ImplKind", "OpBinding", "OpDecl", "OpImpl", "OpRegistry", "SwapReport",
    "global_registry",
    "Container", "DeploymentError", "Runtime",
]
