"""End-to-end metrics: numbers the driver took itself on the host's clock."""


def value(out, ctx, key):
    return out["values"].get(key)
