"""Text completion from token ids (`txt2txt`): a job is rows of prompt ids
and comes back as `{"token_ids": [[...], ...]}`, a row of `max_new_tokens`
ids a sequence, as JSON. No tokenizer on either side of the wire.

`txt2txt_callback` is one job alone; `txt2txt_batched_callback` a gang of
jobs that share a coalesce key (model, prompt bucket, new tokens,
temperature and, for a model that decodes by blocks, `denoising_steps` and
`confidence_threshold`: coalesce.py) as one pass of all their rows. Both return their
ids `Unpackaged`: the JSON is written off the slice, as a pass's images
are (span `artifact_encode`, thread `host`).
"""

from __future__ import annotations

from ..registry import get_pipeline
from ..telemetry import Span
from .diffusion import Unpackaged


class UnpackagedTokens(Unpackaged):
    """A job's ids (host memory: the read-back ended inside the pass) in
    the place of its `artifacts`, until whoever holds them packages."""

    __slots__ = ()

    def __init__(self, token_ids):
        super().__init__(token_ids, ["primary"], "application/json")

    def package(self, spans: list | None = None) -> dict:
        from ..post_processors.output_processor import make_token_result

        with Span("artifact_encode", thread="host", spans=spans):
            return {"primary": make_token_result(self.images)}


def txt2txt_batched_callback(device_identifier: str, requests: list[dict]):
    """The one pass of `requests`, which share everything but their rows
    and seeds: per request `(artifacts, pipeline_config)`, in order. The
    batcher sized the group (`coalesce_rows_limit`); a group that does not
    fit raises, and the worker falls back to its jobs one by one."""
    from ..chips.requirements import check_capacity
    from ..coalesce import prompt_slots

    shared = requests[0]
    model_name = shared["model_name"]
    chipset = shared.get("chipset")
    new_tokens = int(shared["max_new_tokens"])
    rows = [row for request in requests for row in request["prompt_ids"]]
    if chipset is not None:
        positions = prompt_slots(max(len(row) for row in rows)) + new_tokens
        if check_capacity(chipset, model_name, len(rows),
                          positions) < len(rows):
            raise ValueError(
                f"{len(rows)} sequences of {positions} cached positions "
                f"do not fit beside {model_name} on this slice")
    with Span("load"):
        pipeline = get_pipeline(
            model_name, shared.get("pipeline_type", "AutoModelForCausalLM"),
            chipset=chipset)
    # a block decode's two parameters, where the formatter found the
    # family to take them
    blocks = {key: shared[key] for key in (
        "denoising_steps", "confidence_threshold") if key in shared}
    results = pipeline.run_batched(
        [{"prompt_ids": request["prompt_ids"], "rng": request["rng"]}
         for request in requests],
        max_new_tokens=new_tokens,
        temperature=float(shared.get("temperature", 1.0)), **blocks)
    out = []
    for token_ids, pipeline_config in results:
        pipeline_config["batched_with"] = len(requests)
        out.append((UnpackagedTokens(token_ids), pipeline_config))
    return out


def txt2txt_callback(device_identifier: str, model_name: str, **kwargs):
    (result,) = txt2txt_batched_callback(
        device_identifier, [dict(kwargs, model_name=model_name)])
    return result


# the worker runs a group of jobs that all formatted to one callback as one
# pass through that callback's `batched` (worker.py synchronous_do_batch)
txt2txt_callback.batched = txt2txt_batched_callback
