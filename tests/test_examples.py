"""Run every Python example end-to-end against in-process frontends over
real sockets (role of the reference's qa/L0_* example harnesses; the
examples themselves mirror src/python/examples/ of the reference)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES_DIR = os.path.join(REPO, "src", "python", "examples")




# (script, protocol-of-url, extra args)
CASES = [
    ("simple_http_infer_client.py", "http", []),
    ("simple_grpc_infer_client.py", "grpc", []),
    ("simple_http_async_infer_client.py", "http", []),
    ("simple_grpc_async_infer_client.py", "grpc", []),
    ("simple_http_string_infer_client.py", "http", []),
    ("simple_grpc_string_infer_client.py", "grpc", []),
    ("simple_http_health_metadata.py", "http", []),
    ("simple_grpc_health_metadata.py", "grpc", []),
    ("simple_http_model_control.py", "http", []),
    ("simple_grpc_model_control.py", "grpc", []),
    ("simple_http_sequence_sync_infer_client.py", "http", []),
    ("simple_grpc_sequence_sync_infer_client.py", "grpc", []),
    ("simple_grpc_sequence_stream_infer_client.py", "grpc", []),
    ("simple_grpc_custom_args_client.py", "grpc", []),
    ("simple_grpc_keepalive_client.py", "grpc", []),
    ("simple_grpc_custom_repeat.py", "grpc", []),
    ("simple_http_pool_failover.py", "http", ["-n", "24"]),
    ("simple_http_router.py", "http", []),
    ("simple_fleet.py", "http", []),
    ("simple_http_shm_client.py", "http", []),
    ("simple_grpc_shm_client.py", "grpc", []),
    ("simple_http_shm_string_client.py", "http", []),
    ("simple_grpc_shm_string_client.py", "grpc", []),
    ("simple_http_xlashm_client.py", "http", []),
    ("simple_grpc_xlashm_client.py", "grpc", []),
    ("simple_http_aio_infer_client.py", "http", []),
    ("simple_grpc_aio_infer_client.py", "grpc", []),
    ("simple_grpc_aio_sequence_stream_infer_client.py", "grpc", []),
    ("grpc_client.py", "grpc", []),
    ("grpc_explicit_int_content_client.py", "grpc", []),
    ("grpc_explicit_int8_content_client.py", "grpc", []),
    ("grpc_explicit_byte_content_client.py", "grpc", []),
    ("memory_growth_test.py", "http", ["-n", "200"]),
    ("image_client.py", "http", ["--synthetic", "2", "-c", "2"]),
    ("image_client.py", "grpc",
     ["-i", "grpc", "--synthetic", "4", "-b", "2", "-a",
      "-s", "INCEPTION"]),
    ("image_client.py", "grpc",
     ["-i", "grpc", "--synthetic", "1", "--streaming", "-s", "VGG"]),
    ("grpc_image_client.py", "grpc", []),
    ("ensemble_image_client.py", "http", []),
    ("ensemble_image_client.py", "grpc", ["-i", "grpc"]),
    ("reuse_infer_objects_client.py", "http", []),
    ("reuse_infer_objects_client.py", "grpc", ["-i", "grpc"]),
]


@pytest.mark.parametrize(
    "script,proto,extra",
    CASES,
    ids=["{}{}".format(c[0], "-" + "".join(
        a.lstrip("-") for a in c[2] if a.startswith("-")
    ) if c[2] else "") for c in CASES],
)
def test_example(zoo_servers, script, proto, extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src", "python")
    env["JAX_PLATFORMS"] = "cpu"
    result = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, script),
         "-u", zoo_servers[proto]] + extra,
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert result.returncode == 0, (
        script + "\n" + result.stdout + "\n" + result.stderr
    )
    assert "PASS" in result.stdout, result.stdout


@pytest.mark.perf
def test_perf_analyzer_cli_against_live_server(zoo_servers):
    """The perf_analyzer CLI as a user runs it: --backend http against
    a live frontend, tiny windows, table + JSON out."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src", "python")
    env["JAX_PLATFORMS"] = "cpu"
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_analyzer.py"),
         "-m", "simple", "--backend", "http", "-u", zoo_servers["http"],
         "--concurrency-range", "2", "--measurement-interval", "250",
         "--max-trials", "5", "--warmup", "0.1"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert result.returncode == 0, result.stdout + result.stderr
    assert "*** perf_analyzer" in result.stdout
    assert '"unit": "infer/sec"' in result.stdout


def test_llama_streaming_example():
    """Token streaming with KV parked in XLA shm — BASELINE config #5's
    user-facing client (own tiny-llama server; the shared zoo omits
    llama to keep the rest of the suite fast)."""
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    core = InferenceServer([LlamaGenerateModel(cfg=llama.tiny(vocab=256))])
    frontend = GrpcFrontend(core, port=0).start()
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src", "python")
        env["JAX_PLATFORMS"] = "cpu"
        result = subprocess.run(
            [sys.executable,
             os.path.join(EXAMPLES_DIR, "llama_streaming_client.py"),
             "-u", "127.0.0.1:{}".format(frontend.port), "-n", "3"],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert result.returncode == 0, result.stdout + result.stderr
        assert "PASS" in result.stdout
        # the zero-copy plane: prompt by shm reference, tokens read
        # back from the region's ring — identical to the in-band run
        result = subprocess.run(
            [sys.executable,
             os.path.join(EXAMPLES_DIR, "llama_streaming_client.py"),
             "-u", "127.0.0.1:{}".format(frontend.port), "-n", "3",
             "--shared-memory", "xla"],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert result.returncode == 0, result.stdout + result.stderr
        assert "PASS: llama streaming (xla shared memory)" in \
            result.stdout
    finally:
        frontend.stop()


def test_block_diffusion_streaming_example():
    """A block-diffusion answer streamed a block a response, at both
    ``denoising_steps`` (own tiny-SDAR server on the scheduler)."""
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    core = InferenceServer([LlamaGenerateModel(
        cfg=llama.tiny_sdar(), max_seq=64, max_slots=4)])
    frontend = GrpcFrontend(core, port=0).start()
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src", "python")
        env["JAX_PLATFORMS"] = "cpu"
        result = subprocess.run(
            [sys.executable,
             os.path.join(EXAMPLES_DIR, "block_diffusion_streaming_client.py"),
             "-u", "127.0.0.1:{}".format(frontend.port), "-n", "12"],
            capture_output=True, text=True, timeout=600, env=env,
        )
        assert result.returncode == 0, result.stdout + result.stderr
        assert "PASS: block diffusion streaming" in result.stdout
        # 12 tokens twice: delivered tokens and the block step's counters
        text = core.metrics_text()
        assert 'tpu_scheduler_tokens_total{model="llama_generate"} 24' in text
        passes = {
            line.split("{")[0]: float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("tpu_diffusion_")}
        assert passes["tpu_diffusion_tokens_unmasked_total"] >= 24
        # a block's commit rides on the next block's first pass
        assert (passes["tpu_diffusion_row_passes_total"]
                > passes["tpu_diffusion_fused_commits_total"] > 0)
        assert passes["tpu_diffusion_blocks_committed_total"] == (
            passes["tpu_diffusion_fused_commits_total"]
            + passes["tpu_diffusion_commit_passes_total"])
    finally:
        frontend.stop()
        core.close()
