"""What the readers that part `setup_s` share: the program's staging spans
(`xla_trace`, `xla_lower`, `xla_compile`, `xla_cache_read`; jax's own
staging events as `chiaswarm_tpu/compile_cache.py` stamps them) in a
scrape, and the two wall stamps that part set-up into before the worker and
the worker's own start. A program without the spans reads `None`."""

STAGED = ("xla_trace", "xla_lower", "xla_compile")


def stage_s(scraped: dict, stage: str):
    """Seconds the stage's spans sum to in the scrape; `None` where the
    program stamps no staging span at all, 0.0 where it does and this
    stage has had no event (no read-back on a cold start)."""
    sums = scraped.get("swarm_job_stage_seconds_sum", {})
    if "xla_compile" not in sums:
        return None
    return sums.get(stage, 0.0)


def staged_s(scraped: dict):
    """Trace + lower + compile (the read-back lies inside compile)."""
    parts = [stage_s(scraped, stage) for stage in STAGED]
    return None if None in parts else sum(parts)


def worker_wall_s(record: dict):
    """Wall clock from the scrape before the worker exists to the
    window's opening: the worker's start and the warm-up passes."""
    stamps = [record.get(key, {}).get("at_wall", {}).get("")
              for key in ("scrape_before_worker", "scrape_open")]
    return None if None in stamps else stamps[1] - stamps[0]
