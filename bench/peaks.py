"""The chip's published peaks, keyed by JAX's `device_kind`
(`bench/peaks.json`).  A device that is not in the table is an error."""
import json
from pathlib import Path

TABLE = json.loads((Path(__file__).resolve().parent / "peaks.json")
                   .read_text())


def peak(device_kind: str) -> dict:
    if device_kind not in TABLE:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return TABLE[device_kind]
