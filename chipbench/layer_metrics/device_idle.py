"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, averaged over the chips."""

SPECS = [{"name": "device_idle.serve", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "device",
          "moves": "serve_tok_s"},
         {"name": "device_idle.train", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "device",
          "moves": "train_tok_s"}]


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
