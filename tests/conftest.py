"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh so sharding and
model tests run in CI without TPU hardware (multi-chip paths are validated on
host devices; the driver's dryrun does the same)."""

import os

# Force CPU even when the ambient env names an accelerator: unit tests need
# deterministic f32 math and 8 virtual devices for the sharding suite.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache, shared by every test in the tier AND
# primed for the next run on the same checkout. The suite is dominated by
# engine-executable compiles (a ServingEngine build measured 7.3s cold vs
# 2.5s warm on the 2-core CI rig), and tier-1 runs under a hard wall-clock
# budget on shared, throttle-prone runners — caching identical compiles is
# the difference between fitting that budget and flaking on box weather.
# Keyed by exact HLO + flags, so nothing about what is tested changes.
# The directory follows vtpu.util.jaxcache's rule (an ambient
# JAX_COMPILATION_CACHE_DIR wins, else <checkout>/.jax_cache); a test
# that spawns an engine host (test_crosshost) hands its child the same.
from vtpu.util.jaxcache import place_compile_cache  # noqa: E402

place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import shutil  # noqa: E402
import subprocess  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

from vtpu.device.registry import reset_registry  # noqa: E402
from vtpu.util import nodelock  # noqa: E402


@pytest.fixture(scope="session")
def libvtpu_build():
    """Build libvtpu once per session; shared by the native and monitor tests."""
    libvtpu = Path(__file__).resolve().parent.parent / "libvtpu"
    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain")
    r = subprocess.run(["make", "-C", str(libvtpu)], capture_output=True, text=True)
    assert r.returncode == 0, f"libvtpu build failed:\n{r.stdout}\n{r.stderr}"
    return libvtpu / "build"


@pytest.fixture(autouse=True)
def _clean_state():
    reset_registry()
    nodelock.reset_for_test()
    yield
    reset_registry()
    nodelock.reset_for_test()


def _engine_leaks(eng) -> list:
    """The resource invariants every STOPPED engine must satisfy: the
    allocator free list accounts for every block not legitimately pinned
    by a registered prefix, no slot holds a request or blocks, nothing is
    parked or mid-swap, and the host swap pool is fully free. A violation
    is a leak in whatever lifecycle path the test exercised."""
    errs = []
    if getattr(eng, "_alloc", None) is not None:
        pinned = sum(len(e["blocks"]) for e in eng._prefixes.values())
        free = eng._alloc.free_blocks
        total = eng._n_blocks - 1
        if free + pinned != total:
            errs.append(
                f"allocator leak: {free} free + {pinned} prefix-pinned "
                f"!= {total} usable blocks")
    occupied = [i for i, r in enumerate(eng._slot_req) if r is not None]
    if occupied:
        errs.append(f"slots still occupied after stop: {occupied}")
    held = [i for i, b in enumerate(eng._slot_blocks) if b]
    if held:
        errs.append(f"slots still holding blocks after stop: {held}")
    if eng._parked:
        errs.append(f"{len(eng._parked)} sessions still parked after stop")
    if eng._swap_pending:
        errs.append(f"{len(eng._swap_pending)} swap-outs still pending")
    if eng._swap_enabled and len(eng._host_free) != eng._swap_host_blocks:
        errs.append(
            f"host swap pool leak: {len(eng._host_free)} free of "
            f"{eng._swap_host_blocks}")
    if eng._admitting:
        errs.append(f"admissions still in flight: {sorted(eng._admitting)}")
    lq = getattr(eng, "_lifecycle_q", None)
    if lq is not None and not lq.empty():
        # a migrate ticket left unanswered would strand its caller; the
        # engine's shutdown sweep must have failed every outstanding one
        errs.append(f"{lq.qsize()} lifecycle commands unserved after stop")
    return errs


@pytest.fixture(autouse=True)
def leak_check(request):
    """Failure-domain invariant net over EVERY engine-constructing test
    (ISSUE 12 satellite; extended by ISSUE 13): each ServingEngine built
    during the test is stopped at teardown and checked for leaks —
    allocator free list, host swap pool, slot occupancy, parked set,
    unserved lifecycle tickets. EVERY engine the test built is audited —
    for a migration test that means the source AND the destination, so a
    transfer path that leaks blocks on either side fails here. A recovery
    path (shed, fault containment, worker restart, swap loss, migration
    fallback) that forgets to release what a dead request held fails
    HERE, in whatever suite happened to drive it, not only in the
    dedicated fault tests."""
    try:
        from vtpu.serving import engine as _engine_mod
    except Exception:  # minimal environments without the serving deps
        yield
        return
    built: list = []
    orig_init = _engine_mod.ServingEngine.__init__

    def tracking_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        built.append(self)

    _engine_mod.ServingEngine.__init__ = tracking_init
    try:
        yield
    finally:
        _engine_mod.ServingEngine.__init__ = orig_init
    errs = []
    for eng in built:
        try:
            eng.stop()  # idempotent; never-started engines drain inline
        except Exception as exc:  # pragma: no cover - diagnostic only
            errs.append(f"stop() raised: {exc!r}")
            continue
        errs.extend(_engine_leaks(eng))
    assert not errs, "engine resource leaks at teardown: " + "; ".join(errs)
