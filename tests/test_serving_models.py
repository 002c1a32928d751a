"""Tests for the BASELINE-config serving zoo: vision (ResNet-50 /
DenseNet-121), the BERT ensemble, and decoupled llama generation with
KV-cache parking in XLA shm."""

import numpy as np
import pytest

from tpuserver.core import InferenceServer, InferRequest, RequestedOutput


@pytest.fixture(scope="module")
def zoo_core():
    from tpuserver.models import default_models, serving_models
    from tpuserver.models import llama

    models = default_models() + serving_models(
        llama_cfg=llama.tiny(vocab=512)
    )
    return InferenceServer(models)


def _infer(core, model, inputs, requested=None):
    return core.infer(
        InferRequest(model, inputs=inputs, requested_outputs=requested)
    )


def _out(resp, name):
    for spec, array, delivery in resp.outputs:
        if spec["name"] == name:
            return spec, array
    return None, None


def test_resnet50_forward(zoo_core):
    img = np.random.RandomState(0).rand(1, 224, 224, 3).astype(np.float32)
    resp = _infer(zoo_core, "resnet50", {"INPUT": img})
    spec, probs = _out(resp, "OUTPUT")
    assert spec["shape"] == [1, 1000]
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-3)


def test_resnet50_classification_output(zoo_core):
    img = np.random.RandomState(1).rand(1, 224, 224, 3).astype(np.float32)
    resp = _infer(
        zoo_core, "resnet50", {"INPUT": img},
        [RequestedOutput("OUTPUT", class_count=3)],
    )
    spec, classes = _out(resp, "OUTPUT")
    assert spec["datatype"] == "BYTES"
    assert classes.shape == (1, 3)
    # "value:index:label" formatting with our class_<i> labels
    first = classes[0, 0].decode("utf-8")
    parts = first.split(":")
    assert len(parts) == 3 and parts[2].startswith("class_")


def test_densenet121_forward(zoo_core):
    img = np.random.RandomState(2).rand(1, 224, 224, 3).astype(np.float32)
    resp = _infer(zoo_core, "densenet121", {"INPUT": img})
    spec, probs = _out(resp, "OUTPUT")
    assert spec["shape"] == [1, 1000]
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-3)


def test_bert_ensemble(zoo_core):
    text = np.array([b"hello tpu world"], dtype=np.object_)
    resp = _infer(zoo_core, "bert_ensemble", {"TEXT": text})
    spec, pooled = _out(resp, "POOLED")
    assert pooled.shape == (768,)
    assert np.isfinite(pooled).all()
    # deterministic per text, sensitive to text
    resp2 = _infer(zoo_core, "bert_ensemble", {"TEXT": text})
    np.testing.assert_array_equal(_out(resp2, "POOLED")[1], pooled)
    other = np.array([b"a different sentence"], dtype=np.object_)
    resp3 = _infer(zoo_core, "bert_ensemble", {"TEXT": other})
    assert not np.array_equal(_out(resp3, "POOLED")[1], pooled)


def test_bert_tokenizer_shapes(zoo_core):
    text = np.array([b"one two three"], dtype=np.object_)
    resp = _infer(zoo_core, "bert_tokenizer", {"TEXT": text})
    _, ids = _out(resp, "INPUT_IDS")
    _, mask = _out(resp, "ATTENTION_MASK")
    assert ids.shape == (128,)
    assert ids[0] == 101  # [CLS]
    assert mask.sum() == 5  # CLS + 3 words + SEP


def test_llama_generate_stream(zoo_core):
    prompt = np.array([1, 2, 3, 4], dtype=np.int32)
    req = InferRequest(
        "llama_generate",
        inputs={
            "PROMPT_IDS": prompt,
            "MAX_TOKENS": np.array([5], dtype=np.int32),
        },
    )
    tokens = []
    for resp in zoo_core.infer_stream(req):
        _, tok = _out(resp, "TOKEN")
        _, logp = _out(resp, "LOGPROB")
        tokens.append(int(tok[0]))
        assert logp[0] <= 0.0
    assert len(tokens) == 5
    # greedy decode is deterministic
    tokens2 = [
        int(_out(r, "TOKEN")[1][0]) for r in zoo_core.infer_stream(req)
    ]
    assert tokens2 == tokens


def test_llama_generate_kv_cache_region(zoo_core):
    """Park the KV cache in an XLA shm region, resume without re-prefill."""
    from tritonclient.utils import xla_shared_memory as xshm

    cache_handle = xshm.create_shared_memory_region("kv_park", 1 << 20)
    try:
        raw = xshm.get_raw_handle(cache_handle)
        zoo_core.register_xla_shm("kv_park", raw, 0, 1 << 20)
        prompt = np.array([5, 6, 7], dtype=np.int32)
        req = InferRequest(
            "llama_generate",
            inputs={
                "PROMPT_IDS": prompt,
                "MAX_TOKENS": np.array([4], dtype=np.int32),
            },
            parameters={"kv_cache_region": "kv_park"},
        )
        first = [
            int(_out(r, "TOKEN")[1][0]) for r in zoo_core.infer_stream(req)
        ]
        assert len(first) == 4
        # the region now holds a device-resident cache segment
        assert cache_handle.get_jax_segment(0) is not None

        # continue from the parked cache: feed the generated tokens back
        req2 = InferRequest(
            "llama_generate",
            inputs={
                "PROMPT_IDS": np.array(first[-1:], dtype=np.int32),
                "MAX_TOKENS": np.array([3], dtype=np.int32),
            },
            parameters={
                "kv_cache_region": "kv_park",
                "kv_cache_resume": True,
                "kv_cache_position": 3 + 4,
            },
        )
        second = [
            int(_out(r, "TOKEN")[1][0]) for r in zoo_core.infer_stream(req2)
        ]
        assert len(second) == 3
    finally:
        zoo_core.unregister_xla_shm("kv_park")
        xshm.destroy_shared_memory_region(cache_handle)


def test_llama_generate_rejects_overflow(zoo_core):
    from tpuserver.core import ServerError

    req = InferRequest(
        "llama_generate",
        inputs={
            "PROMPT_IDS": np.arange(500, dtype=np.int32),
            "MAX_TOKENS": np.array([100], dtype=np.int32),
        },
    )
    with pytest.raises(ServerError, match="exceeds"):
        list(zoo_core.infer_stream(req))


def test_llama_chunked_decode_matches_per_token():
    """Scanned decode chunks are bit-identical to per-token decode across
    full chunks AND the sub-chunk tail (greedy sampling)."""
    from tpuserver.models import llama as llama_mod
    from tpuserver.models.llama_serving import LlamaGenerateModel

    def tokens_with(chunk, n_tokens):
        core = InferenceServer([
            LlamaGenerateModel(
                cfg=llama_mod.tiny(vocab=256), decode_chunk=chunk)
        ])
        req = InferRequest("llama_generate", inputs={
            "PROMPT_IDS": np.array([1, 2, 3, 4], dtype=np.int32),
            "MAX_TOKENS": np.array([n_tokens], dtype=np.int32),
        })
        out = []
        for resp in core.infer_stream(req):
            for spec, arr, _ in resp.outputs:
                if spec["name"] == "TOKEN":
                    out.append(int(arr[0]))
        return out

    n = 19  # 2 full chunks of 8 + a 3-token tail
    per_token = tokens_with(1, n)
    chunked = tokens_with(8, n)
    assert len(per_token) == n
    assert per_token == chunked

    with pytest.raises(ValueError):
        LlamaGenerateModel(
            cfg=llama_mod.tiny(vocab=256), decode_chunk=0)


def test_llama_generate_pipelined_emission_boundaries():
    """The software-pipelined emission (chunks chained on device, first
    token fetched from prefill logits) must produce exactly max_tokens
    tokens and the SAME tokens for every max_tokens around the chunk
    boundary — prefixes of one greedy sequence."""
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    model = LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, decode_chunk=4)
    core = InferenceServer([model])
    prompt = np.array([9, 8, 7, 6], dtype=np.int32)

    def generate(n):
        req = InferRequest(
            "llama_generate",
            inputs={
                "PROMPT_IDS": prompt,
                "MAX_TOKENS": np.array([n], dtype=np.int32),
            },
        )
        toks = []
        for resp in core.infer_stream(req):
            _, tok = _out(resp, "TOKEN")
            _, logp = _out(resp, "LOGPROB")
            toks.append(int(tok[0]))
            assert logp[0] <= 0.0
        return toks

    # chunk=4: tail-only (3), exactly one chunk (4), chunk+tail (5),
    # early+two chunks (8), and deep into the pipeline (11)
    seqs = {n: generate(n) for n in (3, 4, 5, 8, 11)}
    for n, toks in seqs.items():
        assert len(toks) == n, (n, toks)
    longest = seqs[11]
    for n, toks in seqs.items():
        assert toks == longest[:n], (n, toks, longest)


def test_generate_model_options_are_scheduler_options():
    """Every keyword ``LlamaGenerateModel`` hands ``DecodeScheduler``
    (directly, or through the ``kv_hooks`` it splats) is one of the
    scheduler's own, and the two option counts are the ones ROADMAP.md
    D5 records: whoever adds an option moves the record with it."""
    import ast
    import inspect

    from tpuserver.models import llama_serving
    from tpuserver.scheduler import DecodeScheduler

    tree = ast.parse(inspect.getsource(llama_serving))
    forwarded = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
                node.func, "id", None) == "DecodeScheduler":
            forwarded |= {k.arg for k in node.keywords if k.arg}
        elif (isinstance(node, ast.Assign)
              and getattr(node.targets[0], "id", None) == "kv_hooks"
              and isinstance(node.value, ast.Call)):
            forwarded |= {k.arg for k in node.value.keywords}
    accepted = set(inspect.signature(DecodeScheduler.__init__).parameters)
    assert {"kv_export", "metrics", "prefix_cache"} <= forwarded <= accepted
    offered = inspect.signature(
        llama_serving.LlamaGenerateModel.__init__).parameters
    assert (len(offered) - 1, len(accepted) - 1) == (24, 21)
