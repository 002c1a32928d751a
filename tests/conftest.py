import os
import sys

# Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
# exercised without TPU hardware.  Forced — whatever JAX_PLATFORMS the
# session exports, unit tests must be deterministic and leave the chip
# free; the export below is also what every subprocess a test spawns
# inherits (no launcher defaults the platform in code).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_PY = os.path.join(REPO_ROOT, "src", "python")
if SRC_PY not in sys.path:
    sys.path.insert(0, SRC_PY)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """Chaos/pool tests spin up supervisors, probers, and replay
    machinery; every one of those threads is contractually a *daemon*
    that dies with its owner.  This guard fails the test that leaks a
    NON-daemon thread — the kind that would wedge interpreter shutdown
    — at the source, instead of letting the whole session hang at
    exit."""
    import threading
    import time as _time

    if not (request.node.get_closest_marker("chaos")
            or request.node.get_closest_marker("pool")
            or request.node.get_closest_marker("router")
            or request.node.get_closest_marker("fleet")
            or request.node.get_closest_marker("campaign")
            or request.node.get_closest_marker("spec")):
        yield
        return
    before = {t.ident for t in threading.enumerate()}
    yield
    leaked = []
    deadline = _time.monotonic() + 5.0
    while _time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive() and not t.daemon
        ]
        if not leaked:
            return
        _time.sleep(0.05)  # teardown grace: joins may still be running
    pytest.fail(
        "test leaked non-daemon thread(s): {}".format(
            [t.name for t in leaked]))


@pytest.fixture(scope="session")
def server_core():
    """A shared in-process server core with the fixture model zoo."""
    from tpuserver.core import InferenceServer
    from tpuserver.models import default_models

    return InferenceServer(default_models())


@pytest.fixture(scope="session")
def http_server(server_core):
    from tpuserver.http_frontend import HttpFrontend

    frontend = HttpFrontend(server_core, port=0).start()
    yield frontend
    frontend.stop()


@pytest.fixture(scope="session")
def http_url(http_server):
    return http_server.url


@pytest.fixture(scope="session")
def zoo_servers():
    """HTTP + gRPC frontends over a core with the vision serving zoo —
    shared by the Python/C++ example suites (image/ensemble examples
    need resnet50/image_ensemble; one compile for the whole session)."""
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import default_models, serving_models

    core = InferenceServer(
        default_models()
        + serving_models(include_bert=False, include_llama=False)
    )
    http = HttpFrontend(core, port=0).start()
    grpc_f = GrpcFrontend(core, port=0).start()
    yield {
        "http": http.url.replace("http://", ""),
        "grpc": "127.0.0.1:{}".format(grpc_f.port),
    }
    grpc_f.stop()
    http.stop()
