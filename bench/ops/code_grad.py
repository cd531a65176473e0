"""The compact code-gradient projection backward (``kernels/code_grad.py``:
dx and dW). Its kernels are named; no count of its work is kept yet."""

NAMES = [r"^code_grad_d[xw]\.\d+$"]


def work(cfg, mix):
    return None
