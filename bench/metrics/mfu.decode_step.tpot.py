"""The decode step's share of its roofline (see _decode_roofline.py)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_decode_roofline",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "_decode_roofline.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


def read(ctx):
    return _mod.decode_roofline(ctx)
