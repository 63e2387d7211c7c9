"""Share of the decode batch that did useful work: tokens the decode
program emitted in the window over (decode iterations x slots).  First
tokens come from prefill and are left out."""

SPECS = [{"name": "decode_occupancy", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "scheduler",
          "moves": "serve_tok_s"}]


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_steps"):
        return None
    decoded = c["generated_tokens"] - c["first_tokens_in_window"]
    return 100.0 * decoded / (c["decode_steps"] * c["slots"])
