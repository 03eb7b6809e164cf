"""Seconds the backend really compiled on this start: the `xla_compile`
spans' sum at the window's opening less the `xla_cache_read` spans', which
lie inside them. With `setup_xla_cache_read_s` it makes `compile_s`."""

from benchmark import setup_split


def read(record):
    scraped = record["scrape_open"]
    whole = setup_split.stage_s(scraped, "xla_compile")
    if whole is None:
        return None
    return whole - setup_split.stage_s(scraped, "xla_cache_read")
