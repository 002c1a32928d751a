"""Vision model family: ResNet-50 and DenseNet-121 in pure JAX, TPU-first.

These serve BASELINE configs #2/#3 (the reference drives ResNet-50 /
DenseNet-121 through image_client / shm examples; reference
src/c++/examples/image_client.cc:64-120).  Layout is NHWC (TPU native),
compute dtype bfloat16 with fp32 accumulation in XLA's conv/matmul, batch
norm folded to inference-mode scale/shift.  Weights are randomly
initialized — the framework benches protocol + data-plane + device
round-trip, not ImageNet accuracy.
"""

import threading

import numpy as np

from tpuserver.core import JaxModel, Model, TensorSpec


def _conv(x, w, stride=1, padding="SAME"):
    import jax.numpy as jnp
    from jax import lax

    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def _scale_shift(x, scale, shift):
    # inference-mode batch norm folded into one multiply-add (fused by XLA)
    return x * scale + shift


def _conv_w(key, kh, kw, cin, cout):
    import jax
    import jax.numpy as jnp

    fan_in = kh * kw * cin
    return (
        jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
        * np.sqrt(2.0 / fan_in)
    ).astype(jnp.bfloat16)


def _bn(c):
    import jax.numpy as jnp

    return {
        "scale": jnp.ones((c,), jnp.bfloat16),
        "shift": jnp.zeros((c,), jnp.bfloat16),
    }


def _stem(params, x):
    """Shared 7x7/2 conv stem + 3x3/2 max pool."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = _conv(x, params["stem"]["w"], stride=2)
    x = jax.nn.relu(_scale_shift(x, params["stem"]["bn"]["scale"],
                                 params["stem"]["bn"]["shift"]))
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


class _ImageNetModel(JaxModel):
    """Shared plumbing: bf16 params, NHWC [B,224,224,3] fp32 wire input,
    softmax probabilities [B,1000] out, classification labels."""

    max_batch_size = 32
    # coalesce concurrent b1 requests into one MXU-shaped dispatch: a
    # conv net at batch 1 leaves the systolic array mostly idle.
    # Power-of-two buckets (the batcher default) keep the padding tax
    # under 2x while bounding the compiled-shape set; compiles persist
    # across runs via the XLA compilation cache.
    dynamic_batching = True
    # overlapping executors hide the host<->device sync of one batch
    # behind the next batch's compute (instance_group count analogue)
    instance_count = 4
    inputs = (TensorSpec("INPUT", "FP32", [224, 224, 3]),)
    outputs = (TensorSpec("OUTPUT", "FP32", [1000]),)

    def __init__(self, seed=0):
        super().__init__()
        self._params = None
        self._seed = seed
        self._params_lock = threading.Lock()
        self.labels = {
            "OUTPUT": ["class_{}".format(i) for i in range(1000)]
        }

    def prepare(self):
        # eager param init (outside any jit trace; see JaxModel.prepare)
        self._get_params()

    def _get_params(self):
        if self._params is None:
            with self._params_lock:
                if self._params is None:
                    self._params = self._init_params()
        return self._params

    def jax_fn(self, INPUT):
        import jax
        import jax.numpy as jnp

        params = self._get_params()
        x = INPUT.astype(jnp.bfloat16)
        logits = self._apply(params, x)
        return {
            "OUTPUT": jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        }

    def warmup(self):
        import numpy as np

        # compile every batch shape live traffic can run at: the
        # batcher's buckets (declared, else its power-of-two default)
        # plus batch 1 (parameter-carrying requests bypass the batcher).
        # A cold shape is a conv-net compile landing inside somebody's
        # request; warmed compiles persist in the XLA cache.
        buckets = self.batch_buckets
        if buckets is None and self.dynamic_batching:
            buckets, b = [], 1
            while b < self.max_batch_size:
                buckets.append(b)
                b <<= 1
            buckets.append(self.max_batch_size)
        for b in {1, *(buckets or ())}:
            self.execute(
                {"INPUT": np.zeros((b, 224, 224, 3), np.float32)}, None
            )


class ResNet50Model(_ImageNetModel):
    """ResNet-50 v1.5 (stride-2 in the 3x3 of downsampling bottlenecks).

    Stage plan (3, 4, 6, 3) bottlenecks — the standard 50-layer graph the
    reference benches over TF-Serving/TorchServe (docs/benchmarking.md:121).
    """

    name = "resnet50"
    platform = "jax"
    backend = "jax"

    _STAGES = (3, 4, 6, 3)
    _WIDTHS = (256, 512, 1024, 2048)

    def _init_params(self):
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self._seed)
        conv_w, bn = _conv_w, _bn

        keys = iter(jax.random.split(key, 200))
        params = {
            "stem": {"w": conv_w(next(keys), 7, 7, 3, 64), "bn": bn(64)},
            "stages": [],
        }
        cin = 64
        for stage, (blocks, width) in enumerate(
            zip(self._STAGES, self._WIDTHS)
        ):
            mid = width // 4
            stage_params = []
            for b in range(blocks):
                blk = {
                    "w1": conv_w(next(keys), 1, 1, cin, mid),
                    "bn1": bn(mid),
                    "w2": conv_w(next(keys), 3, 3, mid, mid),
                    "bn2": bn(mid),
                    "w3": conv_w(next(keys), 1, 1, mid, width),
                    "bn3": bn(width),
                }
                if b == 0:
                    blk["proj"] = conv_w(next(keys), 1, 1, cin, width)
                    blk["proj_bn"] = bn(width)
                stage_params.append(blk)
                cin = width
            params["stages"].append(stage_params)
        params["fc"] = {
            "w": (
                jax.random.normal(
                    next(keys), (2048, 1000), jnp.float32
                ) * 0.01
            ).astype(jnp.bfloat16),
            "b": jnp.zeros((1000,), jnp.bfloat16),
        }
        return params

    def _apply(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax

        relu = jax.nn.relu
        x = _stem(params, x)
        for stage, stage_params in enumerate(params["stages"]):
            for b, blk in enumerate(stage_params):
                stride = 2 if (b == 0 and stage > 0) else 1
                shortcut = x
                if "proj" in blk:
                    shortcut = _conv(x, blk["proj"], stride=stride)
                    shortcut = _scale_shift(
                        shortcut, blk["proj_bn"]["scale"],
                        blk["proj_bn"]["shift"],
                    )
                y = relu(_scale_shift(
                    _conv(x, blk["w1"]), blk["bn1"]["scale"],
                    blk["bn1"]["shift"],
                ))
                y = relu(_scale_shift(
                    _conv(y, blk["w2"], stride=stride),
                    blk["bn2"]["scale"], blk["bn2"]["shift"],
                ))
                y = _scale_shift(
                    _conv(y, blk["w3"]), blk["bn3"]["scale"],
                    blk["bn3"]["shift"],
                )
                x = relu(y + shortcut)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return x @ params["fc"]["w"] + params["fc"]["b"]


class DenseNet121Model(_ImageNetModel):
    """DenseNet-121: dense blocks (6, 12, 24, 16), growth rate 32,
    transition compression 0.5 (BASELINE config #3's model)."""

    name = "densenet121"
    platform = "jax"
    backend = "jax"

    _BLOCKS = (6, 12, 24, 16)
    _GROWTH = 32

    def _init_params(self):
        import jax
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self._seed)
        conv_w, bn = _conv_w, _bn

        keys = iter(jax.random.split(key, 400))
        params = {
            "stem": {"w": conv_w(next(keys), 7, 7, 3, 64), "bn": bn(64)},
            "blocks": [],
            "transitions": [],
        }
        c = 64
        for i, layers in enumerate(self._BLOCKS):
            block = []
            for _ in range(layers):
                block.append(
                    {
                        "bn1": bn(c),
                        "w1": conv_w(next(keys), 1, 1, c, 4 * self._GROWTH),
                        "bn2": bn(4 * self._GROWTH),
                        "w2": conv_w(
                            next(keys), 3, 3, 4 * self._GROWTH, self._GROWTH
                        ),
                    }
                )
                c += self._GROWTH
            params["blocks"].append(block)
            if i < len(self._BLOCKS) - 1:
                cout = c // 2
                params["transitions"].append(
                    {"bn": bn(c), "w": conv_w(next(keys), 1, 1, c, cout)}
                )
                c = cout
        params["final_bn"] = bn(c)
        params["fc"] = {
            "w": (
                jax.random.normal(next(keys), (c, 1000), jnp.float32) * 0.01
            ).astype(jnp.bfloat16),
            "b": jnp.zeros((1000,), jnp.bfloat16),
        }
        return params

    def _apply(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax

        relu = jax.nn.relu
        x = _stem(params, x)
        for i, block in enumerate(params["blocks"]):
            for layer in block:
                y = relu(_scale_shift(
                    x, layer["bn1"]["scale"], layer["bn1"]["shift"]
                ))
                y = _conv(y, layer["w1"])
                y = relu(_scale_shift(
                    y, layer["bn2"]["scale"], layer["bn2"]["shift"]
                ))
                y = _conv(y, layer["w2"])
                x = jnp.concatenate([x, y], axis=-1)
            if i < len(params["transitions"]):
                tr = params["transitions"][i]
                x = relu(_scale_shift(
                    x, tr["bn"]["scale"], tr["bn"]["shift"]
                ))
                x = _conv(x, tr["w"])
                x = lax.reduce_window(
                    x, 0.0, lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
                ) / 4.0
        x = relu(_scale_shift(
            x, params["final_bn"]["scale"], params["final_bn"]["shift"]
        ))
        x = jnp.mean(x, axis=(1, 2))
        return x @ params["fc"]["w"] + params["fc"]["b"]


class ImagePreprocessModel(JaxModel):
    """Raw UINT8 pixels -> normalized FP32 network input.

    The preprocessing stage the reference's ensemble_image_client drives as
    the first composing model of its image ensemble (reference
    src/c++/examples/ensemble_image_client.cc); here it is a jitted cast +
    scale so the whole ensemble stays on device.
    """

    name = "image_preprocess"
    platform = "jax"
    backend = "jax"
    max_batch_size = 32
    inputs = (TensorSpec("RAW_IMAGE", "UINT8", [224, 224, 3]),)
    outputs = (TensorSpec("PREPROCESSED", "FP32", [224, 224, 3]),)

    def jax_fn(self, RAW_IMAGE):
        import jax.numpy as jnp

        return {
            "PREPROCESSED": RAW_IMAGE.astype(jnp.float32) / 255.0
        }


class ImageEnsembleModel(Model):
    """RAW_IMAGE -> classification probs via preprocess + ResNet-50
    (ensemble_scheduling; role of the reference's preprocess+classifier
    ensemble in ensemble_image_client.cc).  Plain Model like
    BertEnsembleModel: the core's ensemble dispatch runs the steps, so no
    jit machinery of its own."""

    name = "image_ensemble"
    platform = "ensemble"
    backend = ""
    max_batch_size = 32
    inputs = (TensorSpec("RAW_IMAGE", "UINT8", [224, 224, 3]),)
    outputs = (TensorSpec("OUTPUT", "FP32", [1000]),)
    ensemble_steps = [
        {
            "model_name": "image_preprocess",
            "model_version": -1,
            "input_map": {"RAW_IMAGE": "RAW_IMAGE"},
            "output_map": {"PREPROCESSED": "pixels"},
        },
        {
            "model_name": "resnet50",
            "model_version": -1,
            "input_map": {"INPUT": "pixels"},
            "output_map": {"OUTPUT": "OUTPUT"},
        },
    ]

    def __init__(self):
        super().__init__()
        self.labels = {
            "OUTPUT": ["class_{}".format(i) for i in range(1000)]
        }
