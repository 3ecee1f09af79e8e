"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference test strategy (SURVEY.md §4): multi-node behavior is
tested on one host — here with JAX's virtual CPU devices standing in for a
TPU slice.

Tests run on the CPU backend: ``JAX_PLATFORMS=cpu`` is exported here,
before anything imports jax, so this process and every worker subprocess a
test spawns come up on CPU. The chip is exercised by ``chip_smoke.py``
through the builder's tool, never by this suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import threading  # noqa: E402

import pytest  # noqa: E402

# non-daemon threads a test may legitimately leave behind briefly; matched
# by name prefix after the grace wait below
_THREAD_LEAK_ALLOWLIST = (
    "pytest-",            # pytest-timeout and friends
    "ThreadPoolExecutor",  # pools shut down lazily by gc
)


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    """Every tier-1 test must join the non-daemon threads it starts: a
    leaked non-daemon thread blocks interpreter exit (the DLR009 class,
    caught at runtime). Daemon threads are exempt — the repo's long-lived
    loops are daemons by convention and die with the process."""
    before = {t for t in threading.enumerate() if not t.daemon}
    yield
    deadline = 2.0
    leaked = []
    for t in threading.enumerate():
        if t.daemon or t in before or not t.is_alive():
            continue
        t.join(deadline)  # grace: the test may still be tearing down
        deadline = 0.1
        if t.is_alive() and not any(
            t.name.startswith(p) for p in _THREAD_LEAK_ALLOWLIST
        ):
            leaked.append(t)
    assert not leaked, (
        "non-daemon thread(s) leaked by this test (they would block "
        "interpreter exit — join them on the stop path, or make the loop "
        "a named daemon): "
        + ", ".join(f"{t.name!r} (ident={t.ident})" for t in leaked)
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On any chaos-marked failure, print the fault schedule + seed so the
    run is replayable: export the printed env vars and re-run the test.
    On any analysis-marked failure, print the analyzer repro command."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.failed:
        return
    if item.get_closest_marker("analysis") is not None:
        rep.sections.append((
            "analysis repro",
            "reproduce / triage the lint findings with:\n"
            "  python -m dlrover_tpu.analysis --check\n"
            "fix the new violations, add an inline `# noqa: DLR00X — reason`"
            " for vetted sites, or (deliberate deferral) re-run with"
            " --update-baseline\n",
        ))
    if item.get_closest_marker("chaos") is None:
        return
    try:
        from dlrover_tpu.chaos import active_repro

        repro = active_repro()
    except Exception:  # noqa: BLE001 — reporting must not mask the failure
        repro = None
    if repro:
        rep.sections.append((
            "chaos repro",
            f"replay this fault sequence with:\n  {repro}\n",
        ))


@pytest.fixture
def lock_order_guard():
    """Opt-in runtime lock-order detector: instruments threading.Lock/RLock
    for the duration of the test and fails it if two locks were ever taken
    in contradictory orders (the PR 2 injector-deadlock class). The fixture
    yields the detector so tests can also name locks explicitly via
    ``guard.make_lock("name")``."""
    from dlrover_tpu.analysis.lock_order import LockOrderDetector

    detector = LockOrderDetector()
    detector.install()
    try:
        yield detector
    finally:
        detector.uninstall()
    detector.check()


@pytest.fixture
def race_guard():
    """Opt-in happens-before data-race detector: instruments threading
    primitives + queue handoffs for the duration of the test and fails it
    if any container registered via ``race_detector.shared(...)`` saw two
    accesses unordered by the happens-before relation. The fixture yields
    the detector so tests can register extra state via ``guard.track()``
    and inspect ``guard.races``. Uninstall always runs, even when the
    test body fails, so instrumentation never bleeds across tests."""
    from dlrover_tpu.analysis.race_detector import RaceDetector

    detector = RaceDetector()
    detector.install()
    try:
        yield detector
    finally:
        detector.uninstall()
    detector.check()
