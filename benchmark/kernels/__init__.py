"""Operations and bytes, one module an architecture.

A configuration's file names its module under the key `counts`:
`<benchmark>/kernels/<counts>.py`. Without the key it is `counts.py` here,
the dense Llama-style block. Every module keeps `counts.py`'s rule: what
the model NEEDS, counted from the configuration's shapes, so that a share
of a peak worked out from it can only fall short of what the hardware did.
What the whole-step readers call (`readers/serve_mfu.py`,
`readers/decode_step_hbm.py`):

    prefill_flops(cfg, prompt_len)
    decode_flops(cfg, context_len)
    decode_step_bytes(cfg, active_slots, live_kv_tokens)

and a kernel's roofline (`readers/trace_kernel_roofline.py`) calls the two
functions its `layer_metrics` file names, `f(cfg, active_slots,
live_kv_tokens)` each: the bytes and the operations of ONE call.
"""

from __future__ import annotations

import importlib


def counts_for(cfg: dict):
    """The module the configuration names, `kernels/<counts>.py` beside this
    file; `counts.py` where it names none. (`run.load_cell` has ended the
    run before anything boots where the name finds no file.)"""
    return importlib.import_module("kernels." + cfg.get("counts", "counts"))
