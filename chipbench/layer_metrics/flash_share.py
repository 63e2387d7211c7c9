"""Share of the traced window the training step spends in the flash
attention kernels: the ``device_ops`` entries of the train program whose
Pallas kernel is named ``flash*`` (``flash_fwd*``, ``flash_bwd_*``) over the
traced window.  ``device_ops`` holds the ten largest operations; where no
``flash*`` kernel is among them (or the program does not name its kernels)
there is nothing to read."""

PREFIX = "jit_train_step:mosaic:flash"

SPECS = [{"name": "flash_share.train", "unit": "%", "better": "lower",
          "source": "device_trace", "layer": "kernels",
          "moves": "train_tok_s"}]


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    flash = [s for k, s in t["device_ops"] if k.startswith(PREFIX)]
    return 100.0 * sum(flash) / t["window_s"] if flash else None
