"""Make src/ importable without installation and tests/ self-importable.

``pip install -e .`` is the supported path (see pyproject.toml); this
fallback keeps ``python -m pytest`` working from a bare checkout.
"""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")

for p in (_SRC, _HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

# On hosts whose ISA it supports, XLA's CPU backend hands some dots and
# fusions to YNNPACK, which reduces in another order than XLA's own
# emitters: the bit-identity tests would then depend on the host CPU.
# Read once, when the CPU backend starts.
_YNN_OFF = "--xla_cpu_experimental_ynn_fusion_type="
if "xla_cpu_experimental_ynn_fusion_type" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_YNN_OFF}".strip()
