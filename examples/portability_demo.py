"""Portability demo — the paper's central claim, §V-B/V-C, end to end.

One bundle, three "systems" (platform descriptors), zero modification:

  laptop   : reference ops only (no native features)      — build & test
  cluster  : native collectives available                  — deploy
  tpu-v5e-1chip  : Pallas kernels + native collectives declared  — deploy

For each deployment we print the op-binding report (which ops were
swapped, which refused and why) and verify the model output is IDENTICAL
across deployments — the ratio==1.0 result of Tables III-V.  An
ABI-violating "vendor kernel" is then registered to show the runtime
refusing the swap (libtool-string check) instead of mis-deploying.

Run:  PYTHONPATH=src python examples/portability_demo.py
"""

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import PLATFORMS, Runtime
from repro.core.abi import AbiString
from repro.core.registry import ImplKind, OpImpl, OpRegistry
from repro.kernels.ops import ABIS, OP_NAMES, _REFS  # noqa: F401
from repro.launch.mesh import make_host_mesh
from repro.launch.train import make_bundle
from repro.models import build_model


def deploy_and_run(bundle, platform_name, params, batch):
    rt = Runtime(host_env={})
    container = rt.deploy(
        bundle,
        native_ops=True,
        platform=PLATFORMS[platform_name],
        mesh=make_host_mesh(data=1),
    )
    cfg = ModelConfig.from_dict(container.bundle.model_config)
    model = build_model(cfg, binding=container.binding)
    loss, _ = jax.jit(model.loss_fn)(params, batch)
    swapped = [r.op for r in container.binding.reports if r.swapped]
    refused = [
        (r.op, r.reason) for r in container.binding.reports if not r.swapped
    ]
    rt.cleanup()
    return float(loss), swapped, refused


def main() -> None:
    bundle = make_bundle("qwen2.5-14b", reduced=True)
    cfg = ModelConfig.from_dict(bundle.model_config)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    print(f"bundle: {bundle.reference} (digest {bundle.digest})\n")
    losses = {}
    swapped_by_system = {}
    # tpu-v5e-1chip declares pallas_kernels but requires an actual TPU ("driver
    # loaded") — on this CPU host the swap is refused with a report.
    # pod-sim runs the SAME Pallas kernels through the interpreter, so the
    # swap genuinely happens and the numerics can be compared.
    for system in ("laptop", "cluster", "tpu-v5e-1chip", "pod-sim"):
        loss, swapped, refused = deploy_and_run(bundle, system, params, batch)
        losses[system] = loss
        swapped_by_system[system] = swapped
        print(f"=== {system} ===")
        print(f"  loss = {loss:.6f}")
        print(f"  swapped ops: {swapped or 'none'}")
        for op, reason in refused[:3]:
            print(f"  kept ref {op}: {reason}")
        print()

    assert swapped_by_system["pod-sim"], "pod-sim must swap in the kernels"
    assert not swapped_by_system["tpu-v5e-1chip"], "no TPU present -> swap refused"
    spread = max(losses.values()) - min(losses.values())
    print(f"cross-system loss spread: {spread:.2e} "
          f"(ref vs swapped-kernel numerics agree: {spread < 1e-3})\n")

    # --- ABI refusal demo: a 'vendor kernel' with the wrong signature ----
    reg = OpRegistry()
    for name in OP_NAMES:
        reg.declare(ABIS[name])
        reg.register(OpImpl(abi=ABIS[name], kind=ImplKind.REFERENCE,
                            fn=_REFS[name], provider="jnp-ref"))
    bad_abi = AbiString.make("rmsnorm", {"args": ["x"], "note": "wrong"}, major=1)
    reg.register(
        OpImpl(abi=bad_abi, kind=ImplKind.NATIVE,
               fn=lambda x, w, eps=0: x * 0, requires_feature=None,
               provider="bad-vendor"),
        strict=False,
    )
    binding = reg.bind(["rmsnorm"], PLATFORMS["tpu-v5e-1chip"], native=True, freeze=False)
    report = binding.reports[0]
    print("ABI refusal demo (mismatched vendor rmsnorm):")
    print("  registration refused (libtool-string mismatch logged above);")
    print(f"  swapped={report.swapped}  binding: {report.reason}")
    assert not report.swapped, "runtime must refuse an ABI-incompatible swap"


if __name__ == "__main__":
    main()
