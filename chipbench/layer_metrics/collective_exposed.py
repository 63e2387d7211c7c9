"""Several chips only: share of the traced window in which a collective
operation ran on a chip while no other operation did (device average)."""

SPECS = [{"name": "collective_exposed", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "ZeRO",
          "moves": "train_tok_s"}]


def read(ctx):
    t = ctx["trace"]
    if not t or t["devices"] < 2 or not t["window_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
