"""A family that is no diffusion UNet, for `test_family_seam.py`: a two-layer
network with the whole contract of `families/<family>.py` (README, "A
family"), seconds on the CPU. The tests install it as
`benchmark.families.stub`; it is no family of the benchmark."""

import numpy as np

# float32 against the float64 reference reads 1.5e-7 to 1.9e-7 over twelve
# seeds, weights rounded to 8 bits 0.010 to 0.013 (CPU; test_family_seam.py
# holds both to a factor of three): between the two, with room on both sides.
DENOISER_REL_L2_TOL = 1e-4
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


def denoiser_inputs(pipe, config: dict, seed: int) -> dict:
    CALLS.append("denoiser_inputs")
    rows = int(config["job"].get("rows", ROWS))
    x = np.random.default_rng(seed).normal(size=(rows, WIDTH))
    return {"x": x.astype(np.float32)}


def denoiser_reference(pipe, inputs: dict):
    import jax
    import jax.numpy as jnp

    CALLS.append("denoiser_reference")
    w1, w2 = (pipe.params[k].astype(np.float64) for k in ("w1", "w2"))
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
    params = pipe.params
    if pipe.weight_bits:
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
