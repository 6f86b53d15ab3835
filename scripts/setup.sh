#!/usr/bin/env bash
# Fresh TPU-VM bootstrap for jumbo_mae_tpu_tpu.
#
# Role parity with the reference's env script
# (/root/reference/scripts/setup.sh:15-34), rebuilt for this framework's
# stack: jax[tpu] instead of jax+libtpu-from-releases-page, opencv (SIMD
# JPEG decode in the data workers) instead of Pillow-SIMD, orbax instead of
# nothing, and an optional native build for the C++ tar reader.
#
# Run on each worker VM of the pod slice, e.g.:
#   gcloud compute tpus tpu-vm ssh $TPU_NAME --worker=all \
#     --command="bash jumbo_mae_tpu_tpu/scripts/setup.sh"
set -euo pipefail

# 1. Python deps. jax[tpu] pulls the matching libtpu. Pinned to the minor
#    the code is written for and was run on the chip with (jax/jaxlib 0.9.0,
#    libtpu 0.0.34, flax 0.12.3, optax 0.2.6): the runtime calls that jax's
#    API directly and carries no shims for other versions.
python3 -m pip install -U pip
python3 -m pip install -U "jax[tpu]==0.9.*" \
  -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
python3 -m pip install -U "flax==0.12.*" "optax==0.2.*" chex einops numpy pillow orbax-checkpoint pyyaml

# 2. Fast image decode for the host-side data workers (cv2 uses SIMD
#    libjpeg-turbo wheels; data/decode.py falls back to PIL when absent).
python3 -m pip install -U opencv-python-headless

# 3. Optional extras: wandb metrics sink (utils/logging.py falls back to
#    JSONL without it), pytest for the test suite.
python3 -m pip install -U wandb pytest || true

# 4. Native tar reader (data/native.py; pure-Python tario is the fallback,
#    so this step is optional but recommended for >10GbE shard streaming).
REPO_DIR="$(cd "$(dirname "$0")/.." && pwd)"
if command -v c++ >/dev/null 2>&1; then
  c++ -O2 -shared -fPIC -o "$REPO_DIR/native/libtario.so" "$REPO_DIR/native/tario.cc"
  echo "built native/libtario.so"
else
  echo "no C++ compiler found; skipping native reader (python fallback active)"
fi

# 5. Install the package itself (editable, so recipes resolve relative paths).
python3 -m pip install -e "$REPO_DIR"

python3 - <<'EOF'
import jax
print("jax", jax.__version__, "devices:", jax.devices())
EOF
