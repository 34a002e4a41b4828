"""The counts a configuration of another architecture brings, rehearsed:
the interface of kernels/__init__.py, with numbers that cannot be taken
for counts.py's (an architecture that needs 3, 5 and 7 times the dense
block's operations and bytes), and one kernel's own bytes and operations."""

from kernels import counts


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    return 3 * counts.prefill_flops(cfg, prompt_len)


def decode_flops(cfg: dict, context_len: int) -> int:
    return 5 * counts.decode_flops(cfg, context_len)


def decode_step_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return 7 * counts.decode_step_bytes(cfg, active_slots, live_kv_tokens)


def other_kernel_bytes(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    """A state update: one state of hidden_size x hidden_size a slot, read and written."""
    return 2 * active_slots * cfg["hidden_size"] ** 2 * counts.BYTES[cfg.get("torch_dtype", "bfloat16")]


def other_kernel_flops(cfg: dict, active_slots: float, live_kv_tokens: float) -> float:
    return 4 * active_slots * cfg["hidden_size"] ** 2
