"""Device time one decode step spends in learned sparse attention (an
indexer choosing the keys a query attends), per WHOLE execution of the
decode program, summed over the kernels the program names for it — every
Mosaic kernel called ``paged_index_*`` or ``paged_sparse_*``:

``paged_index_scores``   scoring: the indexer's keys of each row's valid
                         blocks, read in place, against the row's query
``paged_sparse_select``  selection: the exact top-k of those scores as a
                         threshold (no sort: the sampler's ``sort`` is
                         another operation under another name)
``paged_sparse_attn``    the chosen-key read: the row's blocks that hold a
                         chosen key, copied and attended under the
                         selection, which the kernel rebuilds from the
                         scores and the threshold

(``trace_reduce``'s ``custom_call_s`` keys ``<module>:mosaic:<kernel>``; a
later kernel of the mechanism joins the sum by its name, with no edit
here).  Between the kernels XLA moves the scores into token order and
reduces the selection to one flag a block — element-wise passes over one
layer's ``[rows, T, max_seq_len]`` float32 (1 MB in decode, 32 MB a
``[4, 128]`` chunk) inside the program's ``fusion`` time, not counted here
(PERF.md section 5 has their size on the chip).  A program without these
kernels (every model without an indexer, and the parent of the PR that
added them) gives ``None``."""
import re

PROGRAM = r"^jit_decode"
KERNELS = re.compile(r":mosaic:paged_(index|sparse)_\w+$")

SPECS = [{"name": "sparse_attn_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "model step",
          "moves": "serve_tok_s"}]


def per_run_s(trace, program=PROGRAM):
    """Seconds in the kernels above per whole execution of the programs
    matching ``program``, or None."""
    if not trace:
        return None
    rx = re.compile(program)
    mine = [v for k, v in trace["custom_call_s"].items()
            if rx.search(k) and KERNELS.search(k)]
    runs = sum(len(v) for k, v in trace["programs"].items() if rx.search(k))
    if not runs or not mine:
        return None
    return sum(mine) / runs


def read(ctx):
    t = per_run_s(ctx["trace"])
    return None if t is None else t * 1e3
