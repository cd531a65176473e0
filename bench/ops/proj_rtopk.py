"""The fused projection -> [RoPE] -> top-k forward (``kernels/rtopk.py``).
Its kernels are named; no count of its work is kept yet."""

NAMES = [r"^proj_rtopk\.\d+$"]


def work(cfg, mix):
    return None
