"""The benchmark's own span around the program's registry building the
cell's pipeline: shapes from the modules, on-device seeded fill, placement,
tokenizers (`families/<family>.py`)."""


def read(record):
    model = record["spec"]["config"]["job"]["model_name"]
    return (record.get("weights_ready_s") or {}).get(model)
