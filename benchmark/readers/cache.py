"""The serving cache: what the traffic holds against what is reserved."""


def live_share(out, ctx):
    """Keys and values live in a decode step of the traced window (prompt
    and served positions of the slots that decoded, mean over its decode
    steps, counted by the driver from the program's requests) over the
    positions the engine reserves (``max_batch`` x ``max_len``)."""
    w = out["facts"]["traced_work"]
    if not w or not w["decode_steps"]:
        return None
    return 100.0 * w["decode_live_tokens"] / w["decode_steps"] \
        / out["facts"]["cache_slots"]
