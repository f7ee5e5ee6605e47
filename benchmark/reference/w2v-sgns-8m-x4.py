"""``w2v-sgns-8m-x4`` is ``w2v-sgns-4m``'s model at twice the vocabulary: the
same plain reference, loaded from the file beside this one."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_reference_w2v_sgns_4m",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "w2v-sgns-4m.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({k: v for k, v in vars(_mod).items()
                  if not k.startswith("__")})
