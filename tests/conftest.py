"""Test harness.

Mirrors the reference's strategy (SURVEY.md §4): a real in-process control
plane served over real gRPC on localhost — so the full transport stack
(HTTP/2, retries, metadata) is exercised — plus CPU-jax standing in for TPU
via a forced 8-device host platform.

pytest-asyncio isn't available in this environment, so a minimal coroutine
runner hook is provided here.
"""

from __future__ import annotations

import asyncio
import inspect
import os
import sys

# Force JAX onto a virtual 8-device CPU platform BEFORE jax initializes:
# tests never touch a real chip, even on a machine that has one (the
# TPU-gated kernel tests in test_ops.py are run there with --noconftest).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MODAL_TPU_JAX_PLATFORM"] = "cpu"
# hermetic tests: never auto-boot a LocalSupervisor from Client.from_env —
# every test that needs a server runs its own fixture supervisor
os.environ["MODAL_TPU_AUTO_LOCAL_SERVER"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# Fast tier: `pytest -m fast -q` runs these modules in <2 min
# on a small CI box: serialization/foundation, kernels-adjacent pure-python
# units, and one real-gRPC surface per subsystem. Full-stack container tests
# stay in the default tier.
_FAST_MODULES = {
    "test_foundation",
    "test_quant",
    "test_traceback",
    "test_token_flow",
    "test_proxy_ephemeral",
    "test_blob_multipart",
    "test_e2e_function",
    "test_workspace",
    "test_docs_gen",
    "test_cbor",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rpartition(".")[2] in _FAST_MODULES:
            item.add_marker(pytest.mark.fast)


def pytest_pyfunc_call(pyfuncitem):
    """Run `async def` tests on a fresh event loop (pytest-asyncio stand-in)."""
    testfunc = pyfuncitem.obj
    if inspect.iscoroutinefunction(testfunc):
        sig = inspect.signature(testfunc)
        kwargs = {name: pyfuncitem.funcargs[name] for name in sig.parameters if name in pyfuncitem.funcargs}
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(testfunc(**kwargs), timeout=120))
        finally:
            loop.close()
        return True
    return None


@pytest.fixture
def tmp_state_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("MODAL_TPU_STATE_DIR", str(tmp_path / "state"))
    return tmp_path / "state"


def _make_fault_injecting_servicer():
    """Test-only servicer subclass whose legacy `fail_*` knob attributes
    delegate to the supervisor's ChaosPolicy (modal_tpu/chaos.py) — the
    promoted form of the old hand-rolled fault-injecting subclass. Knobs now
    cover BOTH planes: e.g. `fail_put_inputs` fails FunctionPutInputs on the
    control plane AND MapStartOrContinue/AttemptStart on the input plane."""
    from modal_tpu.chaos import KNOB_RPCS
    from modal_tpu.server.services import ModalTPUServicer

    def _knob_property(knob: str) -> property:
        def _get(self):
            return self.chaos.get_knob(knob)

        def _set(self, count: int) -> None:
            self.chaos.set_knob(knob, count)

        return property(_get, _set)

    return type(
        "ChaosKnobServicer",
        (ModalTPUServicer,),
        {knob: _knob_property(knob) for knob in KNOB_RPCS},
    )


@pytest.fixture
def supervisor(tmp_path, monkeypatch):
    """An in-process control plane + 1 worker (real gRPC on localhost),
    running on the synchronizer loop thread so both sync and async tests can
    talk to it. Async fixtures aren't possible without pytest-asyncio, so the
    supervisor is driven through the blocking bridge.

    Carries a zero-rate ChaosPolicy: no faults unless a test flips the
    `servicer.fail_*` knobs (or mutates `sup.chaos` directly), but the chaos
    injection path itself is exercised by every test that uses this fixture."""
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.chaos import ChaosPolicy
    from modal_tpu.client import _Client
    from modal_tpu.server.supervisor import LocalSupervisor

    monkeypatch.setenv("MODAL_TPU_STATE_DIR", str(tmp_path / "state"))
    # worker_chips skips the slow jax-probe subprocess and simulates an
    # 8-chip host; containers run CPU jax with forced device counts.
    sup = LocalSupervisor(
        num_workers=1,
        state_dir=str(tmp_path / "state"),
        worker_chips=8,
        worker_tpu_type="local-sim",
        servicer_cls=_make_fault_injecting_servicer(),
        chaos=ChaosPolicy(seed=0),
    )
    synchronizer.run(sup.start())
    monkeypatch.setenv("MODAL_TPU_SERVER_URL", f"grpc://127.0.0.1:{sup.port}")
    _Client.set_env_client(None)  # force fresh client pointed at this server
    try:
        yield sup
    finally:
        env_client = _Client._client_from_env
        if env_client is not None and not env_client._closed:
            env_client._close()
        _Client.set_env_client(None)
        synchronizer.run(sup.stop())
