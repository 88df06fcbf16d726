"""bls host: padding key slots as a share of all key slots the verify
programs were dispatched with in the window, in percent, from the
program's `lighthouse_tpu_pubkey_slots_total` counter (its `padding`
path beside the live `table`, `overflow` and `packed` ones), read by
the traffic's `drive` around the window. None where the program counts
no padding slots."""


def read(ctx):
    slots = ctx["harness"].get("pubkey_slots") or {}
    total = sum(slots.values())
    if "padding" not in slots or not total:
        return None
    return slots["padding"] / total * 100.0
