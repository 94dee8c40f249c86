"""``compile_s`` (layer: compile): host seconds around ``api.compile``
plus the first timed call's trace, lower and compile, taken as the first
call's seconds less a steady call's."""


def read(record: dict):
    return record["compile_host_s"] + max(
        0.0, record["first_call_s"] - record["steady_call_s"]
    )
