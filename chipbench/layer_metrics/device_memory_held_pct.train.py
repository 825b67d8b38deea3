"""Share of the fullest device's memory the process has held: peak bytes in
use (live arrays) plus peak bytes the runtime reserved for programs'
temporaries, over the device's limit, from the program's own
``telemetry.device_memory()``. ``None`` where the program or the backend
does not report the reserved bytes."""


def read(obs):
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.observability import telemetry
    held = [100.0 * (m["peak_bytes_in_use"] + m["peak_bytes_reserved"])
            / m["bytes_limit"]
            for m in telemetry.device_memory()
            if m.get("available") and m.get("bytes_limit")
            and m.get("peak_bytes_reserved")]
    return max(held) if held else None
