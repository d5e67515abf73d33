"""Runtime — the Shifter Runtime, staged exactly as the paper's §III-A.

    pull/reformat        -> Gateway (separate component, as in Fig. 1)
    prepare environment  -> resolve platform, select+renumber devices,
                            build the mesh, swap ops (native support),
                            specialize kernels from the site tuning
                            cache (autotune) and/or wrap the binding
                            for live workload capture (profile)
    chroot jail          -> Container object: the program sees ONLY the
                            frozen OpBinding and merged env — never the
                            registry or host environment directly
    drop privileges      -> freeze the registry (no rebinding mid-run)
    export env variables -> bundle env ∪ selected host env (host wins on
                            the site-specific allowlist, like Shifter's
                            config-driven variable sourcing)
    execute              -> jit'd step functions run under the mesh
    cleanup              -> thaw registry, release the container

GPU-support trigger semantics (§IV-A) are preserved: accelerator binding
activates only on a *valid* REPRO_VISIBLE_DEVICES; otherwise the container
still runs, on the default (laptop) resources.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax

from repro.core.bundle import Bundle
from repro.core.env import (
    ENV_VISIBLE,
    autotune_default,
    native_ops_default,
    parse_visible_devices,
    profile_default,
    resolve_platform,
    search_budget_default,
    select_devices,
    tuning_bundle_default,
    tuning_max_bytes_default,
    tuning_max_entries_default,
)
from repro.core.platform import Platform
from repro.core.registry import OpBinding, OpRegistry, global_registry

__all__ = ["Runtime", "Container", "DeploymentError"]

log = logging.getLogger("repro.runtime")

# Host variables a container inherits (Shifter: "selected variables from the
# host system are also added", per site configuration).
_HOST_ENV_ALLOWLIST = (ENV_VISIBLE, "REPRO_PLATFORM", "REPRO_CHECKPOINT_DIR",
                       "REPRO_AUTOTUNE",
                       "REPRO_TUNING_CACHE", "REPRO_PROFILE",
                       "REPRO_WORKLOAD_PROFILE", "REPRO_SEARCH_BUDGET",
                       "REPRO_TUNING_MAX_ENTRIES", "REPRO_TUNING_MAX_BYTES",
                       "REPRO_TUNING_BUNDLE")


class DeploymentError(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Container:
    """A deployed program: the chroot'd view of the world.

    Everything the program may touch is here — ops come exclusively from
    ``binding`` (the bind-mounted libraries), resources from ``mesh``, and
    configuration from ``env``/``bundle``.
    """

    bundle: Bundle
    platform: Platform
    mesh: jax.sharding.Mesh
    binding: OpBinding
    env: Mapping[str, str]
    native_ops: bool
    autotune: bool = False
    profile: bool = False
    workload: Any = None   # tuning.WorkloadProfile capturing this
    # container's op geometries; None unless profiling is on.  Persisted
    # by Runtime.cleanup().
    tuning_imports: Any = None   # tuning.bundle.ImportReport of the
    # tuning-bundle import that ran before binding; None when no bundle
    # was given (or its import was rejected — the rejection is logged).

    @property
    def devices(self) -> tuple[jax.Device, ...]:
        return tuple(self.mesh.devices.flat)

    def describe(self) -> str:
        head = (
            f"container {self.bundle.reference} (digest {self.bundle.digest})\n"
            f"  platform: {self.platform.name} ({self.platform.description})\n"
            f"  mesh: shape={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
            f"devices={self.mesh.devices.size}\n"
            f"  native ops: {'enabled' if self.native_ops else 'disabled'}"
            f" | autotune: {'on' if self.autotune else 'off'}"
            f" | profile: {'on' if self.profile else 'off'}\n"
        )
        return head + self.binding.describe()


class Runtime:
    """Deploys bundles onto a site.  One Runtime per process, like `shifter`.

    Args:
      registry: the op registry to bind from; defaults to the process
        global one (populated by ``repro.kernels.ops.register_all``).
      host_env: the site environment consulted for every ``REPRO_*``
        trigger variable (see core/env.py) and forwarded to the
        container through the allowlist; defaults to ``os.environ``.
        Tests pass an explicit dict for hermeticity.

    One container may be active at a time; ``deploy`` raises
    DeploymentError if called again before ``cleanup``.
    """

    def __init__(
        self,
        registry: OpRegistry | None = None,
        host_env: Mapping[str, str] | None = None,
    ) -> None:
        self.registry = registry if registry is not None else global_registry
        self.host_env = dict(os.environ if host_env is None else host_env)
        self._active: Container | None = None

    # ------------------------------------------------------------------ #
    def deploy(
        self,
        bundle: Bundle,
        *,
        native_ops: bool | None = None,
        platform: Platform | None = None,
        mesh: jax.sharding.Mesh | None = None,
        devices: Sequence[jax.Device] | None = None,
        extra_ops: Iterable[str] = (),
        freeze: bool = True,
        autotune: bool | None = None,
        autotune_ops: Iterable[str] | None = None,
        autotune_top_k: int = 3,
        search_budget: int | None = None,
        max_tuned_entries: int | None = None,
        tuning_bundle: str | os.PathLike | None = None,
        profile: bool | None = None,
    ) -> Container:
        """Run the preparation stages and hand back the executable Container.

        Args:
          native_ops: the ``--mpi`` flag (None -> REPRO_NATIVE_OPS env
            default); ``mesh`` may be injected by launchers that already
            built the production mesh (dryrun/train), otherwise one is
            derived from the platform topology and the visible devices.
          autotune: (None -> REPRO_AUTOTUNE env default) opts this
            deployment into the site tuning cache: bound native kernels
            get their block configs from REPRO_TUNING_CACHE, searching
            (and persisting the winner) on a miss.  When the site also
            has a workload profile (REPRO_WORKLOAD_PROFILE) with recorded
            traffic, the binding is *geometry-dispatched*: every op's
            top-K observed buckets (plus any further warmed cache
            entries) are resolved into a per-geometry config table, and
            each call picks its entry at trace time — a
            ``repro.tuning.warm``-ed cache replays a shape-polymorphic
            deployment with zero misses and zero searches.  Entries
            tuned against an older kernel ABI revision are expired and
            re-searched, with the eviction noted in the SwapReport
            ("cache-expired-searched").
          autotune_ops: restricts which ops may pay the search cost;
            cache hits and default fallbacks always apply and are
            recorded per-op in the binding's SwapReports.  When None and
            the site has recorded traffic, selection is profile-driven:
            ops bind hottest-first so any search budget is spent where
            traffic actually goes, with each op's rank recorded in its
            SwapReport (``search_rank``).
          autotune_top_k: recorded geometries per op entering the
            dispatch table (mirrors ``repro.tuning.warm --top``).
          search_budget: (None -> REPRO_SEARCH_BUDGET env default) cap on
            how many searches this deploy may pay; misses beyond it bind
            the platform default ("search-budget-exhausted").
          max_tuned_entries: (None -> REPRO_TUNING_MAX_ENTRIES env
            default) per-op cap on the geometry-dispatch table — the
            bounded tuning-state mode.  Each op binds at most this many
            buckets, hottest first; cached entries beyond the cap are
            LRU-evicted under pressure (tombstoned, persisted at flush)
            and surfaced as "cache-evicted-lru" in the SwapReport, so a
            warmed redeploy over more recorded buckets than the cap
            provably keeps exactly the K hottest.  bf16 traffic landing
            on a capped table that only holds fp32 buckets dispatches
            via the "near-dtype" borrow instead of the shipped default.
          tuning_bundle: (None -> REPRO_TUNING_BUNDLE env default, then
            the run bundle's own ``tuning_bundle`` reference) path of a
            portable tuning bundle (repro.tuning.bundle) to auto-import
            into the site cache BEFORE binding.  Every entry is
            revalidated against this platform: feasible entries land
            first-class and bind as "bundle-imported" geometries;
            structurally-matched-but-infeasible ones are demoted to
            penalized dispatch candidates ("bundle-demoted", never bound
            raw); structurally foreign buckets are rejected per entry
            ("bundle-rejected" in the SwapReport).  A corrupt, tampered,
            wrong-schema, or ABI-major-incompatible artifact is rejected
            wholesale — the site cache stays byte-identical and the
            deployment continues cold with a warning (the CLI import, by
            contrast, exits non-zero).
          profile: (None -> REPRO_PROFILE env default) captures every op
            invocation's shape bucket + dtype into the site workload
            profile (under jit: once per compiled geometry, at trace
            time).  The profile is persisted by ``cleanup()``; an
            unwritable profile path degrades to a warning, never an
            error.

        Raises DeploymentError when the site cannot satisfy a bundle-
        required ABI at all, no devices are visible, or a container is
        already active in this Runtime.
        """
        if self._active is not None:
            raise DeploymentError(
                "a container is already running in this Runtime; cleanup() first"
            )

        # -- stage: prepare software environment ---------------------------
        if native_ops is None:
            native_ops = native_ops_default(self.host_env)
        vis = parse_visible_devices(self.host_env.get(ENV_VISIBLE))
        if platform is None:
            platform = resolve_platform(self.host_env, devices)
        if mesh is None:
            mesh = self._make_mesh(platform, vis, devices)

        # ABI verification against the bundle's requirements: the runtime
        # refuses deployment if the site cannot satisfy a required contract
        # at all (no reference either) — a missing libmpi, not a bad swap.
        required = bundle.required_abis()
        for op, want in required.items():
            try:
                decl = self.registry.decl(op)
            except KeyError as e:
                raise DeploymentError(f"site provides no op '{op}'") from e
            why = want.why_incompatible(decl.abi)
            if why is not None:
                raise DeploymentError(
                    f"bundle requires {want} but site declares {decl.abi}: {why}"
                )

        ops = list(required) + [o for o in extra_ops if o not in required]

        # -- stage: workload capture (live geometry profiling) ---------------
        if profile is None:
            profile = profile_default(self.host_env)
        workload = None
        if profile:
            from repro.tuning import WorkloadProfile, resolve_profile_path

            profile_path = resolve_profile_path(self.host_env)
            workload = WorkloadProfile.load(profile_path)
            log.info("profiling on: workload profile %s (%d geometries)",
                     profile_path, len(workload))

        # -- stage: tuning-bundle import (portable site artifacts) -----------
        # The shipped artifact lands in the site cache before the binding
        # reads it, so a laptop-warmed bundle turns a cold cluster deploy
        # into a zero-search one.  Rejections degrade to a warning: a bad
        # artifact must not kill a deployment that can still run cold.
        if tuning_bundle is None:
            tuning_bundle = tuning_bundle_default(self.host_env)
        if tuning_bundle is None:
            tuning_bundle = bundle.tuning_bundle
        bundle_report = None
        if tuning_bundle:
            from repro.tuning import resolve_cache_path
            from repro.tuning.bundle import BundleFormatError, import_bundle

            try:
                bundle_report = import_bundle(
                    tuning_bundle,
                    cache_path=resolve_cache_path(self.host_env),
                    platform=platform, registry=self.registry,
                )
                log.info("tuning bundle %s: %s", tuning_bundle,
                         bundle_report.describe().splitlines()[0])
            except (BundleFormatError, OSError) as e:
                log.warning("tuning bundle %s rejected: %s (site cache "
                            "untouched; deploying cold)", tuning_bundle, e)

        # -- stage: site specialization (deferred kernel tuning) -------------
        if autotune is None:
            autotune = autotune_default(self.host_env)
        tuning_ctx = None
        if autotune:
            from repro.tuning import (
                TuningCache,
                TuningContext,
                WorkloadProfile,
                resolve_cache_path,
                resolve_profile_path,
            )

            cache_path = resolve_cache_path(self.host_env)
            # key tuning on observed traffic whenever the site has a
            # profile — captured by this deployment or a previous one
            tune_profile = workload
            if tune_profile is None:
                recorded = WorkloadProfile.load(resolve_profile_path(self.host_env))
                tune_profile = recorded if len(recorded) else None
            # expiry must compare against the ABI cache keys are written
            # under — the bound tunable native's, which may carry a newer
            # minor than the declaration
            current_abis = {}
            for op in ops:
                native = self.registry.decl(op).tunable_native(platform)
                if native is not None:
                    current_abis[op] = native.abi
            if search_budget is None:
                search_budget = search_budget_default(self.host_env)
            if max_tuned_entries is None:
                max_tuned_entries = tuning_max_entries_default(self.host_env)
            priority = None
            if autotune_ops is None and tune_profile is not None:
                # profile-driven selection: bind (and therefore search)
                # the hottest ops first, so a bounded search budget is
                # spent where traffic actually goes; unprofiled ops keep
                # their relative order after the hot ones
                totals = tune_profile.op_totals()
                hot = sorted((op for op in ops if totals.get(op)),
                             key=lambda o: (-totals[o], o))
                ops = hot + [op for op in ops if op not in set(hot)]
                priority = {op: i + 1 for i, op in enumerate(hot)}
            site_cache = TuningCache.load(cache_path)
            # byte-denominated bound on the cache FILE (distinct from the
            # per-op table cap below): enforced when the flush saves, so
            # one deploy cannot grow the site file past the site's budget
            site_cache.max_bytes = tuning_max_bytes_default(self.host_env)
            tuning_ctx = TuningContext(
                site_cache, platform,
                ops=autotune_ops if autotune_ops is None else set(autotune_ops),
                profile=tune_profile,
                current_abis=current_abis,
                top_k=autotune_top_k,
                search_budget=search_budget,
                priority=priority,
                max_entries=max_tuned_entries,
                bundle_report=bundle_report,
            )
            log.info("autotune on: cache %s (%d entries%s%s%s)",
                     cache_path, len(tuning_ctx.cache),
                     ", profile-keyed" if tune_profile is not None else "",
                     f", search budget {search_budget}"
                     if search_budget is not None else "",
                     f", table cap {max_tuned_entries}"
                     if max_tuned_entries is not None else "")

        binding = self.registry.bind(ops, platform, native=native_ops,
                                     freeze=freeze, tuning=tuning_ctx)
        if tuning_ctx is not None:
            tuning_ctx.flush()   # persist winners + expirations atomically
        if workload is not None:
            from repro.tuning import profiled_binding

            binding = profiled_binding(binding, workload)
        for r in binding.reports:
            log.info("bind %-18s %s", r.op, r.reason)

        # -- stage: export of environment variables -------------------------
        env = dict(bundle.env)
        for key in _HOST_ENV_ALLOWLIST:
            if key in self.host_env:
                env[key] = self.host_env[key]

        container = Container(
            bundle=bundle,
            platform=platform,
            mesh=mesh,
            binding=binding,
            env=env,
            native_ops=native_ops,
            autotune=autotune,
            profile=profile,
            workload=workload,
            tuning_imports=bundle_report,
        )
        self._active = container
        return container

    # ------------------------------------------------------------------ #
    def cleanup(self) -> None:
        """Release the container: persist the workload profile (if this
        deployment was capturing), thaw the registry, clear the jit caches.

        A profile that cannot be written is logged and dropped — losing
        observability data must never fail the workload that produced it.
        """
        if self._active is not None and self._active.workload is not None:
            workload = self._active.workload
            if workload.dirty:
                try:
                    path = workload.save()
                    log.info("workload profile persisted: %s (%d geometries)",
                             path, len(workload))
                except OSError as e:
                    log.warning("could not persist workload profile %s: %s",
                                workload.path, e)
        self._active = None
        self.registry.thaw()
        jax.clear_caches()

    # ------------------------------------------------------------------ #
    def _make_mesh(
        self,
        platform: Platform,
        vis,
        devices: Sequence[jax.Device] | None,
    ) -> jax.sharding.Mesh:
        """Build the execution mesh from the visible, renumbered devices.

        Mirrors §IV-A.3: logical coordinates always start at 0; the mesh is
        shaped by the platform topology, truncated to a prefix shape if
        fewer devices are visible (a container built for 1 GPU runs on a
        multi-GPU host and vice versa).
        """
        import numpy as np

        pool = select_devices(vis, devices)
        if not pool:
            raise DeploymentError("no visible devices after renumbering")
        want = platform.num_devices
        if len(pool) >= want:
            chosen = pool[:want]
            shape = platform.mesh_shape
            axes = platform.mesh_axes
        else:
            # degrade to a 1-D data mesh over what is actually visible
            chosen = pool
            shape = (len(pool),)
            axes = ("data",)
        arr = np.array(chosen, dtype=object).reshape(shape)
        return jax.sharding.Mesh(arr, axes)
