"""Unit tests for the cross-job micro-batching layer (batching.py):
compatibility keying, linger-window grouping, size/capacity caps, and the
queue-compatible accounting the worker's poll gating relies on."""

import asyncio

import pytest

from chiaswarm_tpu.batching import BatchScheduler, coalesce_key, job_rows

TINY_JOB = {
    "id": "job-1",
    "workflow": "txt2img",
    "model_name": "stabilityai/stable-diffusion-2-1",
    "prompt": "a red cube",
    "height": 64,
    "width": 64,
    "num_inference_steps": 2,
    "parameters": {"test_tiny_model": True},
}


def job(**overrides) -> dict:
    j = {k: (dict(v) if isinstance(v, dict) else v) for k, v in TINY_JOB.items()}
    params = overrides.pop("parameters", None)
    if params is not None:
        j["parameters"].update(params)
    j.update(overrides)
    return j


# --- coalesce_key ---


def test_compatible_jobs_share_a_key():
    a = coalesce_key(job())
    b = coalesce_key(job(id="job-2", prompt="a blue sphere", seed=7,
                         num_images_per_prompt=3))
    assert a is not None
    assert a == b


def test_per_row_fields_stay_out_of_the_key():
    # prompt/negative/seed/image-count are per-row payload, not bucket
    base = coalesce_key(job())
    assert coalesce_key(job(negative_prompt="blurry")) == base
    assert coalesce_key(job(seed=123456)) == base


@pytest.mark.parametrize("variant", [
    {"workflow": "img2img"},
    {"workflow": "echo"},
    {"start_image_uri": "http://x/i.png"},
    {"mask_image_uri": "http://x/m.png"},
    {"refiner": {"model_name": "x"}},
    {"upscale": True},
    # a ControlNet without a shareable control image (per-job start-image
    # conditioning) stays on the single path
    {"parameters": {"controlnet": {"preprocessor": "canny"}}},
    {"parameters": {"pipeline_type": "StableDiffusionImg2ImgPipeline"}},
    # unknown passthrough parameters are per-job behavior we refuse to
    # guess at: single path
    {"parameters": {"aesthetic_score": 9.0}},
    # flux WITHOUT explicit guidance: the solo default is variant-
    # dependent (3.5, vs the UNet families' 7.5), so the key refuses
    {"model_name": "black-forest-labs/FLUX.1-dev"},
    {"model_name": ""},
])
def test_unbatchable_jobs_key_to_none(variant):
    assert coalesce_key(job(**variant)) is None


# --- ISSUE 20 satellite: flux joins the coalesce vocabulary ---


def flux_job(**overrides) -> dict:
    j = job(model_name="black-forest-labs/FLUX.1-schnell",
            parameters={"pipeline_type": "FluxPipeline",
                        "guidance_scale": 3.5})
    params = overrides.pop("parameters", None)
    if params is not None:
        j["parameters"].update(params)
    j.update(overrides)
    return j


def test_flux_jobs_coalesce():
    a = coalesce_key(flux_job())
    b = coalesce_key(flux_job(id="job-2", prompt="a blue sphere", seed=7,
                              num_images_per_prompt=3))
    assert a is not None
    assert a == b
    # and never with the UNet families on the same canvas
    assert a != coalesce_key(job())


@pytest.mark.parametrize("variant", [
    {"lora": "style-a"},           # no adapter delta path in the MMDiT
    {"workflow": "img2img", "start_image_uri": "http://x/i.png",
     "strength": 0.5},             # no coalesced img2img variant
    {"parameters": {"controlnet": {
        "control_image_uri": "http://x/c.png"}}},
    {"num_inference_steps": None},  # variant-dependent solo default
    {"parameters": {"guidance_scale": None}},
    {"parameters": {"pipeline_type": "StableDiffusionPipeline"}},
])
def test_unbatchable_flux_jobs_key_to_none(variant):
    j = flux_job(**variant)
    if j.get("num_inference_steps") is None:
        j.pop("num_inference_steps", None)
    if j["parameters"].get("guidance_scale") is None:
        j["parameters"].pop("guidance_scale", None)
    assert coalesce_key(j) is None


def test_flux_guidance_and_steps_split_the_bucket():
    base = coalesce_key(flux_job())
    assert coalesce_key(
        flux_job(parameters={"guidance_scale": 7.0})) != base
    assert coalesce_key(flux_job(num_inference_steps=4)) != base


def test_flux_text_budget_is_a_key_dimension():
    """`max_sequence_length` is a static shape of flux's batched program:
    naming the default (512) shares the bucket of leaving it out, another
    budget is another bucket, and a UNet job that names it stays solo."""
    base = coalesce_key(flux_job())
    assert coalesce_key(
        flux_job(parameters={"max_sequence_length": 512})) == base
    short = coalesce_key(flux_job(parameters={"max_sequence_length": 256}))
    assert short is not None and short != base
    assert coalesce_key(job(parameters={"max_sequence_length": 256})) is None


# --- ISSUE 13: adapter-aware coalescing ---


def test_lora_jobs_coalesce_with_plain_jobs():
    # adapter identity rides per row: a LoRA job shares the plain bucket
    base = coalesce_key(job())
    assert base is not None
    assert coalesce_key(job(lora="style-a")) == base
    assert coalesce_key(job(lora="style-b")) == base


def test_runtime_delta_kill_switch_unbatches_adapter_jobs(monkeypatch):
    # lora_runtime_delta=0 restores the pre-ISSUE-13 serving shape:
    # adapter jobs go back to the single path (run_batched would refuse
    # the group anyway), while plain jobs keep coalescing
    monkeypatch.setenv("CHIASWARM_LORA_RUNTIME_DELTA", "0")
    assert coalesce_key(job(lora="style-a")) is None
    assert coalesce_key(job()) is not None
    monkeypatch.setenv("CHIASWARM_LORA_RUNTIME_DELTA", "1")
    assert coalesce_key(job(lora="style-a")) == coalesce_key(job())


def test_declared_tiny_ranks_share_the_min_bucket():
    # ranks at or below the padded minimum all run as the same rank-4
    # program, so they must share one bucket (and one gang)
    r1 = coalesce_key(job(lora="a", parameters={"lora_rank": 1}))
    r4 = coalesce_key(job(lora="b", parameters={"lora_rank": 4}))
    assert r1 is not None
    assert r1 == r4


def test_declared_rank_bucket_splits():
    base = coalesce_key(job())
    r16 = coalesce_key(job(lora="a", parameters={"lora_rank": 16}))
    r9 = coalesce_key(job(lora="b", parameters={"lora_rank": 9}))
    assert r16 is not None and r16 != base
    assert r9 == r16  # 9 rounds up into the 16 bucket
    assert coalesce_key(job(lora="c", parameters={"lora_rank": 4})) != r16


def test_adapter_ref_spellings():
    from chiaswarm_tpu.coalesce import adapter_ref

    assert adapter_ref(job()) is None
    assert adapter_ref(job(lora="style-a")) == "style-a"
    resolved = adapter_ref(job(lora={"lora": "~/lora", "weight_name":
                                     "style-a", "subfolder": None}))
    assert "style-a" in resolved


def test_shared_controlnet_jobs_coalesce():
    cn = {"controlnet_model_name": "lllyasviel/sd-controlnet-canny",
          "control_image_uri": "http://x/qr.png"}
    a = coalesce_key(job(parameters={"controlnet": dict(cn)}))
    b = coalesce_key(job(id="job-2", seed=9,
                         parameters={"controlnet": dict(cn)}))
    assert a is not None and a == b
    # a different control image (or model) is a different bucket
    other = coalesce_key(job(parameters={"controlnet": dict(
        cn, control_image_uri="http://x/other.png")}))
    assert other is not None and other != a
    # and never the plain-txt2img bucket
    assert a != coalesce_key(job())
    # ControlNet + adapter stays on the single path
    assert coalesce_key(job(lora="a",
                            parameters={"controlnet": dict(cn)})) is None


@pytest.mark.parametrize("variant", [
    {"num_inference_steps": 8},
    {"height": 128, "width": 128},
    {"parameters": {"scheduler_type": "EulerDiscreteScheduler"}},
    {"parameters": {"guidance_scale": 1.0}},
    {"parameters": {"test_tiny_model": False}},
    {"model_name": "stabilityai/stable-diffusion-xl-base-1.0"},
])
def test_shape_and_guidance_changes_split_the_bucket(variant):
    assert coalesce_key(job(**variant)) != coalesce_key(job())
    assert coalesce_key(job(**variant)) is not None


def test_malformed_values_fall_back_to_single_path():
    assert coalesce_key(job(height="tall", width="wide")) is None
    assert coalesce_key(job(parameters={"guidance_scale": "lots"})) is None


def test_job_rows():
    assert job_rows(job()) == 1
    assert job_rows(job(num_images_per_prompt=3)) == 3
    assert job_rows(job(parameters={"num_images_per_prompt": 2})) == 2
    assert job_rows(job(num_images_per_prompt="many")) == 1


# --- BatchScheduler ---


def run(coro):
    return asyncio.run(coro)


def test_linger_coalesces_compatible_jobs():
    async def scenario():
        b = BatchScheduler(linger_s=0.02, max_coalesce=8)
        for i in range(3):
            await b.put(job(id=f"j{i}", prompt=str(i)))
        group = await asyncio.wait_for(b.get(), 1.0)
        return group

    group = run(scenario())
    assert [j["id"] for j in group] == ["j0", "j1", "j2"]


def test_unbatchable_jobs_dispatch_immediately():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)  # linger = never
        await b.put({"id": "e1", "workflow": "echo", "model_name": "none"})
        return await asyncio.wait_for(b.get(), 1.0)

    assert [j["id"] for j in run(scenario())] == ["e1"]


def test_incompatible_groups_stay_separate():
    async def scenario():
        b = BatchScheduler(linger_s=0.02, max_coalesce=8)
        await b.put(job(id="small"))
        await b.put(job(id="big", height=128, width=128))
        first = await asyncio.wait_for(b.get(), 1.0)
        second = await asyncio.wait_for(b.get(), 1.0)
        return first, second

    first, second = run(scenario())
    assert {j["id"] for j in first} | {j["id"] for j in second} == \
        {"small", "big"}
    assert len(first) == len(second) == 1


def test_max_coalesce_releases_full_group_early():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=2)
        for i in range(2):
            await b.put(job(id=f"j{i}"))
        # full group must release WITHOUT waiting out the 60 s linger
        group = await asyncio.wait_for(b.get(), 1.0)
        assert b.pending_jobs == 0
        return group

    assert [j["id"] for j in run(scenario())] == ["j0", "j1"]


def test_capacity_cap_bounds_group_rows():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8,
                           rows_limit=lambda job: 4)
        await b.put(job(id="three", num_images_per_prompt=3))
        # 3 + 2 > 4: the open group must release before admitting this one
        await b.put(job(id="two", num_images_per_prompt=2))
        first = await asyncio.wait_for(b.get(), 1.0)
        # 2 + 2 >= 4 releases the second group at capacity
        await b.put(job(id="two-more", num_images_per_prompt=2))
        second = await asyncio.wait_for(b.get(), 1.0)
        return first, second

    first, second = run(scenario())
    assert [j["id"] for j in first] == ["three"]
    assert [j["id"] for j in second] == ["two", "two-more"]


def test_coalescing_disabled_by_knobs():
    async def scenario(**kw):
        b = BatchScheduler(**kw)
        await b.put(job(id="a"))
        await b.put(job(id="b"))
        return await asyncio.wait_for(b.get(), 1.0), \
            await asyncio.wait_for(b.get(), 1.0)

    for kw in ({"linger_s": 0.0}, {"max_coalesce": 1}):
        first, second = run(scenario(**kw))
        assert len(first) == len(second) == 1


def test_outstanding_accounting_backs_poll_gating():
    async def scenario():
        b = BatchScheduler(linger_s=0.01, max_coalesce=8, maxsize=2)
        await b.put(job(id="a"))
        await b.put(job(id="b"))
        assert b.full()
        group = await asyncio.wait_for(b.get(), 1.0)
        for _ in group:
            b.task_done()
        assert not b.full()
        return group

    assert len(run(scenario())) == 2


def test_flush_all_releases_lingering_groups():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)
        await b.put(job(id="a"))
        assert b.pending_jobs == 1
        b.flush_all()
        assert b.pending_jobs == 0
        return await asyncio.wait_for(b.get(), 1.0)

    assert [j["id"] for j in run(scenario())] == ["a"]


# --- priority fast-path (ROADMAP "priority-aware batching", minimal slice) ---


def test_interactive_job_flushes_its_group_immediately():
    from chiaswarm_tpu.batching import _FLUSHES

    async def scenario():
        before = _FLUSHES.value(reason="priority")
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)  # linger = never
        await b.put(job(id="patient"))
        await b.put(job(id="hurry", priority="interactive"))
        # the interactive job takes its whole lingering group with it NOW
        group = await asyncio.wait_for(b.get(), 1.0)
        assert b.pending_jobs == 0
        assert _FLUSHES.value(reason="priority") == before + 1
        return group

    assert [j["id"] for j in run(scenario())] == ["patient", "hurry"]


def test_sdaas_priority_spelling_and_solo_interactive():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8)
        await b.put(job(id="vip", sdaas_priority="interactive"))
        return await asyncio.wait_for(b.get(), 1.0)

    assert [j["id"] for j in run(scenario())] == ["vip"]


def test_non_interactive_priority_values_still_linger():
    async def scenario():
        b = BatchScheduler(linger_s=0.02, max_coalesce=8)
        await b.put(job(id="a", priority="batch"))
        await b.put(job(id="b"))
        return await asyncio.wait_for(b.get(), 1.0)

    # an unrecognized priority value changes nothing: both coalesce after
    # the linger window as before
    assert [j["id"] for j in run(scenario())] == ["a", "b"]


def test_img2img_jobs_with_start_images_share_a_key():
    a = coalesce_key(job(workflow="img2img",
                         start_image_uri="http://x/a.png", strength=0.6))
    b = coalesce_key(job(id="job-2", workflow="img2img", prompt="other",
                         start_image_uri="http://x/b.png", strength=0.6,
                         seed=9))
    assert a is not None
    assert a == b  # per-request start images ride OUTSIDE the key
    # txt2img and img2img never share a bucket
    assert a != coalesce_key(job())


@pytest.mark.parametrize("variant", [
    # strength shapes the program (scan start index): different bucket
    {"strength": 0.3},
])
def test_img2img_strength_splits_the_bucket(variant):
    base = job(workflow="img2img", start_image_uri="http://x/a.png",
               strength=0.6)
    other = dict(base, **variant)
    assert coalesce_key(base) is not None
    assert coalesce_key(other) is not None
    assert coalesce_key(base) != coalesce_key(other)


@pytest.mark.parametrize("variant", [
    {"start_image_uri": None},  # img2img without a start image: formatter
                                # will fail it per-job — single path
    {"height": None, "width": None},  # no explicit canvas: the solo path
                                      # sizes the pass to each image
    {"model_name": "timbrooks/instruct-pix2pix"},  # edit arch (3-row CFG)
    {"model_name": "runwayml/stable-diffusion-inpainting"},  # 9ch arch
    {"mask_image_uri": "http://x/m.png"},
])
def test_unbatchable_img2img_variants_key_to_none(variant):
    base = dict(job(workflow="img2img", start_image_uri="http://x/a.png",
                    strength=0.6))
    base.update(variant)
    assert coalesce_key(base) is None


def test_top_level_tiny_flag_splits_the_bucket():
    # the tiny stand-in flag rides at either level on the wire; a real
    # job must never coalesce behind a tiny-flagged one (the whole group
    # runs on one model)
    plain = job(parameters={"test_tiny_model": False})
    top_level = dict(plain, test_tiny_model=True)
    assert coalesce_key(top_level) is not None
    assert coalesce_key(top_level) != coalesce_key(plain)
    # ...and it matches the params-level spelling's bucket behavior
    assert coalesce_key(dict(plain, test_tiny_model=True)) == \
        coalesce_key(dict(top_level))


def test_zero_strength_keys_distinctly():
    # strength 0.0 is falsy but meaningful (keep ~the whole start image);
    # it must key apart from the 0.75 default, never be rewritten
    zero = coalesce_key(job(workflow="img2img",
                            start_image_uri="http://x/a.png", strength=0.0))
    default = coalesce_key(job(workflow="img2img",
                               start_image_uri="http://x/a.png"))
    assert zero is not None and zero != default


def test_default_strength_buckets_with_explicit_default():
    implicit = coalesce_key(job(workflow="img2img",
                                start_image_uri="http://x/a.png"))
    explicit = coalesce_key(job(workflow="img2img",
                                start_image_uri="http://x/a.png",
                                strength=0.75))
    assert implicit == explicit


def test_flush_reason_counters_cover_release_paths():
    from chiaswarm_tpu.batching import _FLUSHES, _GROUP_JOBS

    async def scenario():
        solo = _FLUSHES.value(reason="solo")
        size = _FLUSHES.value(reason="size")
        linger = _FLUSHES.value(reason="linger")
        groups = _GROUP_JOBS.count()
        b = BatchScheduler(linger_s=0.02, max_coalesce=2)
        await b.put({"id": "e", "workflow": "echo", "model_name": "none"})
        await b.put(job(id="a"))
        await b.put(job(id="b"))  # completes a max_coalesce=2 group
        await b.put(job(id="c"))  # left to the linger timer
        for _ in range(3):
            await asyncio.wait_for(b.get(), 1.0)
        assert _FLUSHES.value(reason="solo") == solo + 1
        assert _FLUSHES.value(reason="size") == size + 1
        assert _FLUSHES.value(reason="linger") == linger + 1
        assert _GROUP_JOBS.count() == groups + 3

    run(scenario())


@pytest.mark.parametrize("linger_s, max_coalesce, replies, causes", [
    # (jobs the reply brings, seconds to the poll loop's next timed poll,
    # jobs still lingering once the reply is admitted)
    pytest.param(60.0, 8, [(1, 61.0, 0)], {"no_poll_due": 1},
                 id="a-lone-job-no-poll-can-reach-is-on-the-board-at-once"),
    pytest.param(60.0, 8, [(4, 61.0, 0)], {"no_poll_due": 1},
                 id="a-reply-of-four-is-one-group-of-four"),
    pytest.param(60.0, 8, [(1, 0.1, 1), (1, 61.0, 0)], {"no_poll_due": 1},
                 id="a-linger-that-outlasts-the-poll-is-joined-by-the-next"),
    pytest.param(0.05, 8, [(1, 0.01, 1)], {"timer": 1},
                 id="a-linger-that-outlasts-the-poll-ends-at-its-timer"),
    pytest.param(60.0, 2, [(2, 61.0, 0)], {"full": 1},
                 id="a-full-group-is-released-on-put"),
])
def test_a_linger_ends_where_no_poll_can_bring_a_batchmate(
        linger_s, max_coalesce, replies, causes):
    """ISSUE 58: `linger_s` is the longest a group waits. Once the poll loop
    has put a whole reply (`reply_admitted`), a group whose linger ends
    before the next timed poll goes to the board at once; one whose linger
    outlasts the poll period stays open; nothing leaves between two puts
    of one reply unless the group fills; and each release counts once
    under its cause."""
    from chiaswarm_tpu.batching import _FLUSHES, _RELEASES

    async def scenario():
        counted = {c: _RELEASES.value(cause=c)
                   for c in ("full", "timer", "no_poll_due")}
        by_reason = _FLUSHES.value(reason="no_poll_due")
        b = BatchScheduler(linger_s=linger_s, max_coalesce=max_coalesce)
        put = 0
        for brings, next_poll_in, lingering in replies:
            for _ in range(brings):
                await b.put(job(id=f"j{put}", prompt=str(put)))
                put += 1
                assert b.ready_jobs == 0 or "full" in causes
            b.reply_admitted(
                asyncio.get_running_loop().time() + next_poll_in)
            assert b.pending_jobs == lingering
        # well inside the 60 s lingers: no timer brought these
        group = await asyncio.wait_for(b.get(), 1.0)
        assert [j["id"] for j in group] == [f"j{i}" for i in range(put)]
        assert b.pending_jobs == b.ready_jobs == 0
        for cause, before in counted.items():
            assert _RELEASES.value(cause=cause) == before + causes.get(
                cause, 0), cause
        assert _FLUSHES.value(reason="no_poll_due") == by_reason + causes.get(
            "no_poll_due", 0)

    run(scenario())


# --- dispatch board: placement-aware claiming (residency routing) ---


def _placement_rig(chips_per_job=4, linger_s=0.01, **kw):
    """(allocator, scheduler) wired the way the worker wires them."""
    from chiaswarm_tpu.chips import allocator as alloc_mod
    from chiaswarm_tpu.chips.allocator import SliceAllocator

    alloc_mod.reset_residency()
    alloc = SliceAllocator(chips_per_job=chips_per_job)  # 8/4 = 2 slices
    b = BatchScheduler(linger_s=linger_s, max_coalesce=8,
                       free_slices=lambda: alloc.free_count, **kw)
    alloc.add_free_listener(b.notify)
    return alloc, b


def test_claim_routes_resident_model_home_and_steals_for_foreign():
    """The acceptance scenario: with model M resident on slice 0, a
    second M group lands on slice 0 (affinity) while a foreign-model
    group — M is resident elsewhere from ITS point of view, F from the
    slice's — takes the idle slice 1; when M's home is busy, an M group
    steals the idle slice instead of waiting. All observable through
    swarm_placement_total."""
    from chiaswarm_tpu.batching import _PLACEMENT
    from chiaswarm_tpu.chips import allocator as alloc_mod

    async def scenario():
        before = {o: _PLACEMENT.value(outcome=o)
                  for o in ("affinity", "steal", "cold")}
        alloc, b = _placement_rig()

        # 1. first M group: resident nowhere -> cold
        await b.put(job(id="m1"))
        jobs1, cs1, out1 = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert [j["id"] for j in jobs1] == ["m1"] and out1 == "cold"
        # the registry's load event (emulated): M is now warm on cs1
        alloc_mod.note_resident("test/tiny-sd", cs1.slice_id)
        alloc.release(cs1)

        # 2. second M group with both slices free -> home slice (affinity)
        await b.put(job(id="m2"))
        jobs2, cs2, out2 = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert out2 == "affinity" and cs2.slice_id == cs1.slice_id

        # 3. while M's home is busy with m2, a foreign-model group claims
        # the idle slice (cold: F has no home anywhere)...
        await b.put(job(id="f1", model_name="stabilityai/stable-diffusion-xl-base-1.0"))
        jobs3, cs3, out3 = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert out3 == "cold" and cs3.slice_id != cs1.slice_id
        alloc.release(cs3)

        # 4. ...and a further M group steals the idle slice rather than
        # waiting for its busy home (cross-slice batch stealing)
        await b.put(job(id="m3", num_inference_steps=7))
        jobs4, cs4, out4 = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert out4 == "steal" and cs4.slice_id != cs1.slice_id
        alloc.release(cs2)
        alloc.release(cs4)

        deltas = {o: _PLACEMENT.value(outcome=o) - before[o]
                  for o in ("affinity", "steal", "cold")}
        assert deltas == {"affinity": 1, "steal": 1, "cold": 2}
        for jobs in (jobs1, jobs2, jobs3, jobs4):
            for _ in jobs:
                b.task_done()

    run(scenario())


def test_claim_blocked_on_busy_slices_resumes_on_release():
    """A board entry with every slice leased dispatches the moment a
    slice frees — via the allocator's free listener, no polling."""

    async def scenario():
        alloc, b = _placement_rig(chips_per_job=8)  # ONE slice
        await b.put(job(id="first"))
        _, held, _ = await asyncio.wait_for(b.claim(alloc), 2.0)
        await b.put(job(id="second", num_inference_steps=9))
        claim2 = asyncio.create_task(b.claim(alloc))
        await asyncio.sleep(0.05)
        assert not claim2.done()  # work ready, no slice -> waiting
        alloc.release(held)
        jobs, cs, _ = await asyncio.wait_for(claim2, 2.0)
        assert [j["id"] for j in jobs] == ["second"]
        alloc.release(cs)

    run(scenario())


def test_concurrent_slice_workers_claim_distinct_groups():
    """N workers racing the board: every group is claimed exactly once,
    and jobs are never duplicated or dropped."""

    async def scenario():
        alloc, b = _placement_rig(chips_per_job=2, linger_s=0.005)  # 4 slices
        seen: list[str] = []

        async def worker_loop():
            while True:
                jobs, cs, _ = await b.claim(alloc)
                await asyncio.sleep(0.01)  # overlap the claims
                seen.extend(j["id"] for j in jobs)
                alloc.release(cs)
                for _ in jobs:
                    b.task_done()

        workers = [asyncio.create_task(worker_loop()) for _ in range(4)]
        ids = []
        for i in range(10):
            # distinct step counts -> distinct groups -> 10 work items
            await b.put(job(id=f"j{i}", num_inference_steps=i + 1))
            ids.append(f"j{i}")
        for _ in range(200):
            if sorted(seen) == sorted(ids):
                break
            await asyncio.sleep(0.01)
        for w in workers:
            w.cancel()
        await asyncio.gather(*workers, return_exceptions=True)
        assert sorted(seen) == sorted(ids)

    run(scenario())


def test_interactive_group_claims_before_older_groups():
    async def scenario():
        alloc, b = _placement_rig(chips_per_job=8)  # ONE slice
        await b.put(job(id="old", num_inference_steps=3))
        await asyncio.sleep(0.03)  # old group flushes to the board first
        await b.put(job(id="vip", priority="interactive"))
        jobs, cs, _ = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert [j["id"] for j in jobs] == ["vip"]  # jumps the queue
        alloc.release(cs)
        jobs, cs, _ = await asyncio.wait_for(b.claim(alloc), 2.0)
        assert [j["id"] for j in jobs] == ["old"]
        alloc.release(cs)

    run(scenario())


# --- interactive preemption ACROSS groups (ROADMAP item) ---


def test_interactive_arrival_preempts_other_lingering_group():
    from chiaswarm_tpu.batching import _FLUSHES

    async def scenario():
        before = _FLUSHES.value(reason="preempt")
        # one free slice reported: contended -> lingering groups flush
        b = BatchScheduler(linger_s=60.0, max_coalesce=8,
                           free_slices=lambda: 1)
        await b.put(job(id="patient", num_inference_steps=9))  # lingers
        await b.put(job(id="hurry", priority="interactive"))
        assert b.pending_jobs == 0  # BOTH groups flushed
        assert _FLUSHES.value(reason="preempt") == before + 1
        first = await asyncio.wait_for(b.get(), 1.0)
        second = await asyncio.wait_for(b.get(), 1.0)
        return first, second

    first, second = run(scenario())
    # the interactive group is on the board; board order still serves it
    # first through claim() (rule 1) even though FIFO get() may not
    assert {j["id"] for j in first} | {j["id"] for j in second} == \
        {"patient", "hurry"}


def test_interactive_does_not_preempt_when_slices_are_plentiful():
    async def scenario():
        b = BatchScheduler(linger_s=60.0, max_coalesce=8,
                           free_slices=lambda: 3)
        await b.put(job(id="patient", num_inference_steps=9))
        await b.put(job(id="hurry", priority="interactive"))
        # nothing contends: the other group keeps lingering for batchmates
        assert b.pending_jobs == 1
        group = await asyncio.wait_for(b.get(), 1.0)
        assert [j["id"] for j in group] == ["hurry"]
        b.flush_all()

    run(scenario())


def test_unbatchable_interactive_job_also_preempts():
    from chiaswarm_tpu.batching import _FLUSHES

    async def scenario():
        before = _FLUSHES.value(reason="preempt")
        b = BatchScheduler(linger_s=60.0, max_coalesce=8,
                           free_slices=lambda: 0)
        await b.put(job(id="patient"))
        await b.put({"id": "vip-echo", "workflow": "echo",
                     "model_name": "none", "priority": "interactive"})
        assert b.pending_jobs == 0
        assert _FLUSHES.value(reason="preempt") == before + 1

    run(scenario())


def test_flush_stamps_linger_and_claim_spans_on_each_job():
    """ISSUE 8, ISSUE 38: waiting for batchmates and waiting for a slice
    are told apart by two spans the batcher stamps on every job of a
    group (legacy hive or not): `linger` from the job's own arrival to
    the group's release, `claim` from there to the claim, end to start;
    the trace context keeps `coalesced_with` and nothing else of it."""
    import asyncio

    from chiaswarm_tpu.batching import BatchScheduler

    def tiny(job_id, with_trace=True):
        job = {"id": job_id, "workflow": "txt2img",
               "model_name": "stabilityai/stable-diffusion-2-1",
               "prompt": job_id, "height": 64, "width": 64,
               "parameters": {"test_tiny_model": True}}
        if with_trace:
            job["trace"] = {"id": job_id, "attempt": 1}
        return job

    async def scenario():
        from chiaswarm_tpu import telemetry
        from chiaswarm_tpu.batching import ARRIVED, SPANS

        stages = telemetry.REGISTRY.histogram(
            telemetry.STAGE_METRIC, labelnames=("stage",))
        before = stages.count(stage="linger")
        sched = BatchScheduler(linger_s=10.0, max_coalesce=2)
        await sched.put(tiny("t-1"))
        await asyncio.sleep(0.05)
        await sched.put(tiny("t-2", with_trace=False))  # size flush at 2
        await asyncio.sleep(0.02)
        group = await sched.get()
        assert [j["id"] for j in group] == ["t-1", "t-2"]
        trace = group[0]["trace"]
        assert trace["coalesced_with"] == 1 and "lingered_s" not in trace
        assert "trace" not in group[1]
        first, second = ({s["name"]: s for s in j[SPANS]} for j in group)
        for found, job in ((first, group[0]), (second, group[1])):
            assert list(found) == ["linger", "claim"]
            assert found["linger"]["start_wall"] == job[ARRIVED]
            assert found["claim"]["start_wall"] == pytest.approx(
                found["linger"]["start_wall"] + found["linger"]["seconds"],
                abs=1e-6)
            assert found["claim"]["seconds"] >= 0.02
        # the first job waited for its batchmate, the second for nobody
        assert first["linger"]["seconds"] >= 0.05
        assert second["linger"]["seconds"] < 0.02
        assert first["claim"] == second["claim"]  # the group's, shared
        assert stages.count(stage="linger") == before + 2

    asyncio.run(scenario())


def test_linger_grows_with_the_keys_pass_not_with_its_compile(monkeypatch):
    """PR 27: a batchmate one poll away is worth 1/40 of a long pass. The
    linger of a key is the fixed one until two passes have been seen (the
    first carries the compile), then LINGER_PASS_SHARE of the SHORTEST,
    never less than the fixed one; a pass's jobs count once."""
    from chiaswarm_tpu import batching

    async def one_pass(b, ids, seconds, clock):
        for job_id in ids:
            await b.put(job(id=job_id))
        group = await asyncio.wait_for(b.get(), 1.0)
        clock[0] += seconds
        for member in group:
            b.task_done(member)

    async def scenario(monkeypatch_clock):
        b = BatchScheduler(linger_s=0.01, max_coalesce=2)
        key = coalesce_key(job())
        assert b.linger_for(key) == 0.01
        await one_pass(b, ["a", "b"], 100.0, monkeypatch_clock)  # compile
        assert b.linger_for(key) == 0.01  # one pass of two jobs, not two
        await one_pass(b, ["c", "d"], 8.0, monkeypatch_clock)
        assert b.linger_for(key) == pytest.approx(
            8.0 * batching.LINGER_PASS_SHARE)
        await one_pass(b, ["e", "f"], 0.2, monkeypatch_clock)
        assert b.linger_for(key) == 0.01  # a short pass: the fixed linger
        assert not b._claimed_at

    import types

    clock = [1000.0]
    import time

    # the scheduler's clock only (its spans' wall stamps stay real)
    monkeypatch.setattr(batching, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0], time=time.time))
    run(scenario(clock))


def test_a_group_the_linger_formed_says_which_jobs_rode_together():
    """PR 27: jobs the worker joined itself carry one `trace.gang` (`by:
    worker`) in their trace context, as a hive gang does, so the envelopes
    tell the passes that ran; a lone job gets none."""
    def traced(job_id):
        return job(id=job_id, trace={"id": job_id, "attempt": 1})

    async def scenario():
        b = BatchScheduler(linger_s=0.02, max_coalesce=8)
        await b.put(traced("p-1"))
        await b.put(traced("p-2"))
        pair = await asyncio.wait_for(b.get(), 1.0)
        await b.put(traced("lone"))
        lone = await asyncio.wait_for(b.get(), 1.0)
        return pair, lone

    pair, lone = run(scenario())
    gangs = [member["trace"]["gang"] for member in pair]
    assert gangs[0]["id"] == gangs[1]["id"]
    assert [g["index"] for g in gangs] == [0, 1]
    assert all(g["size"] == 2 and g["by"] == "worker" for g in gangs)
    assert "gang" not in lone[0]["trace"]
