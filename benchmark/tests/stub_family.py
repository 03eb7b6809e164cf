"""A family that is no diffusion UNet and returns no picture, for
`test_family_seam.py`: a two-layer network with the whole contract of
`families/<family>.py` (README, "A family"), seconds on the CPU. Its jobs
carry a question and an input picture's address and come back as text, the
JSON `post_processors.output_processor.make_text_result` writes; its own
kernel is one matmul. The tests install it as `benchmark.families.stub`; it
is no family of the benchmark."""

import hashlib
import json

import numpy as np

PIPELINE_TYPE = "BlipForConditionalGeneration"

# float32 against the float64 reference reads 1.5e-7 to 1.9e-7 over twelve
# seeds, weights rounded to 8 bits 0.010 to 0.013 (CPU; test_family_seam.py
# holds both to a factor of three): between the two, with room on both sides.
DENOISER_REL_L2_TOL = 1e-4
# The stub's own kernel, a float32 matmul against float64, max abs error:
# 3e-7 to 5e-7 over twelve seeds, from bfloat16 operands 0.03 to 0.05
# (CPU; test_family_seam.py holds both to a factor of three).
STUB_MATMUL_TOL = 1e-4
WIDTH, HIDDEN, ROWS = 32, 64, 4
CALLS: list[str] = []  # the contract's names, in the order they were called


class StubPipeline:
    """`weight_bits` = 8 is the control: the same network served from
    weights rounded to 8 bits."""

    mesh = None

    def __init__(self, seed: int, weight_bits: int | None = None):
        rng = np.random.default_rng(seed)
        self.params = {
            "w1": rng.normal(size=(WIDTH, HIDDEN)).astype(np.float32)
            / np.sqrt(WIDTH),
            "w2": rng.normal(size=(HIDDEN, WIDTH)).astype(np.float32)
            / np.sqrt(HIDDEN)}
        self.weight_bits = weight_bits


def register(seed: int, record: dict) -> None:
    CALLS.append("register")
    record["stub_seed"] = seed


def job_fields(rng, traffic: dict, count: int, probe: bool) -> dict:
    """A question of `traffic["words"]` words drawn from its vocabulary
    (the probe: the traffic file's own), about the one input picture."""
    if probe:
        return {"prompt": traffic["probe"]["prompt"]}
    words = [rng.choice(traffic["vocabulary"])
             for _ in range(int(traffic["words"]))]
    return {"prompt": f"{' '.join(words)} {count}?"}


def check_artifact(blob: bytes, ref: dict, config: dict) -> str | None:
    """Text: hashes to its name, is JSON, holds a caption that is a
    string and not empty."""
    if hashlib.sha256(blob).hexdigest() != ref.get("sha256"):
        return "text artifact does not hash to its name"
    try:
        caption = json.loads(blob)["caption"]
    except (ValueError, KeyError, TypeError):
        return "text artifact is no JSON object with a caption"
    if not isinstance(caption, str) or not caption.strip():
        return f"caption is {caption!r}, not a line of text"
    return None


def stub_matmul(a, b, dtype):
    """The kernel under test; `dtype` below float32 is the control."""
    import jax.numpy as jnp

    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def kernel_checks(config: dict, dtype, interpret: bool = False):
    """The family's own comparison only (it dispatches neither shared
    kernel): the stub matmul at `config["stub_matmul_shapes"]` against
    float64 numpy."""
    CALLS.append("kernel_checks")
    failures, readings = [], []
    for n, (m, k, cols) in enumerate(config.get("stub_matmul_shapes", ())):
        rng = np.random.default_rng(300 + n)
        a = rng.normal(size=(m, k)).astype(np.float32)
        b = rng.normal(size=(k, cols)).astype(np.float32) / np.sqrt(k)
        got = np.asarray(stub_matmul(a, b, dtype), np.float64)
        err = float(np.abs(got - a.astype(np.float64) @ b).max())
        readings.append({"stub_matmul": [m, k, cols], "max_abs": err,
                         "limit": STUB_MATMUL_TOL})
        if not err <= STUB_MATMUL_TOL:
            failures.append(f"stub matmul {m}x{k}x{cols}: max abs error "
                            f"{err:.2g} over {STUB_MATMUL_TOL}")
    return failures, readings


def _params(pipe, inputs: dict) -> dict:
    """The stub network is the stub's own: handed the program's resident
    pipeline (the rehearsal of a whole cell), it still serves its seeded
    two layers, which `denoiser_inputs` then carries."""
    return inputs.get("params") or pipe.params


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    CALLS.append("denoiser_inputs")
    rows = int(config["job"].get("rows", ROWS))
    x = np.random.default_rng(seed).normal(size=(rows, WIDTH))
    inputs = {"x": x.astype(np.float32)}
    if not isinstance(pipe, StubPipeline):
        inputs["params"] = StubPipeline(seed).params
    return inputs


def denoiser_reference(pipe, inputs: dict):
    import jax
    import jax.numpy as jnp

    CALLS.append("denoiser_reference")
    params = _params(pipe, inputs)
    w1, w2 = (params[k].astype(np.float64) for k in ("w1", "w2"))
    want = np.tanh(inputs["x"].astype(np.float64) @ w1) @ w2
    return jax.device_put(jnp.asarray(want, jnp.float32),
                          jax.local_devices(backend="cpu")[0])


def _rounded(w: np.ndarray, bits: int) -> np.ndarray:
    scale = np.abs(w).max() / (2 ** (bits - 1) - 1)
    return (np.round(w / scale) * scale).astype(np.float32)


def denoiser_serve(pipe, inputs: dict):
    import jax
    import jax.numpy as jnp

    CALLS.append("denoiser_serve")
    params = _params(pipe, inputs)
    if getattr(pipe, "weight_bits", None):
        params = {k: _rounded(w, pipe.weight_bits)
                  for k, w in params.items()}
    serve = jax.jit(lambda p, x: jnp.tanh(x @ p["w1"]) @ p["w2"])
    return serve(params, inputs["x"])


def compile_operands(spec: dict, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    CALLS.append("compile_operands")
    one = SingleDeviceSharding(devices[0])

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one)

    program = jax.jit(lambda p, x: jnp.tanh(x @ p["w1"]) @ p["w2"])
    args = ({"w1": shape(WIDTH, HIDDEN), "w2": shape(HIDDEN, WIDTH)},
            shape(ROWS, WIDTH))
    return program, args, ROWS
