"""gossip decode: the program's `Signature.from_bytes` per signature, in
microseconds (benchmark timer around the calls, summed over the window)."""


def read(ctx):
    h = ctx["harness"]
    if not h.get("decode_n"):
        return None
    return h["decode_s"] / h["decode_n"] * 1e6
