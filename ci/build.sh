#!/usr/bin/env bash
# Build the native components from source (the ci/build.sh analog of the
# reference: ci/build.sh + test/CMakeLists.txt:13-50). Today that is the
# QAP placement solver; the script fails if the native path is
# unavailable rather than silently falling back to pure Python.
set -euo pipefail
cd "$(dirname "$0")/.."

python - <<'EOF'
from stencil_tpu import qap  # builds _build/libstencil_qap-<hash>.so
assert qap.native_available(), "native QAP solver failed to load"
import numpy as np
w = np.array([[0.0, 2.0], [2.0, 0.0]])
d = np.array([[0.0, 1.0], [1.0, 0.0]])
f, cost = qap.solve(w, d)
assert sorted(f) == [0, 1] and cost == 4.0, (f, cost)
print("native QAP solver OK")
EOF
