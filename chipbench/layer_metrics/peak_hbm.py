"""Peak device memory of the fullest chip, set-up included, in GB (1e9
bytes): the run's ``memory_peak_bytes`` (``run._memory_peak``: the larger
of ``peak_bytes_in_use`` and ``bytes_in_use + bytes_reserved``, the space
the runtime reserves for the loaded programs' temporaries)."""

SPECS = [{"name": "peak_hbm.serve", "unit": "GB", "better": "lower",
          "source": "program_counter", "layer": "device",
          "moves": "serve_tok_s"},
         {"name": "peak_hbm.train", "unit": "GB", "better": "lower",
          "source": "program_counter", "layer": "device",
          "moves": "train_tok_s"}]


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
