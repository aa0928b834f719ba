"""PJRT C-API smoke surface (native/tnd_pjrt.cpp; SURVEY §2.9 N1/N13).

The C++ runtime drives a real PJRT plugin with no Python in the loop:
dlopen + GetPjrtApi + version negotiation run everywhere; client creation,
H2D/D2H and compile+execute require attached hardware, so those run when a
plugin can actually initialize and skip (with the plugin's own error) when
not — e.g. on a host with no chip libtpu reports "No jellyfish device
found".

Runs in a subprocess: libtpu does not tolerate re-initialization in a
process that may later (or already did) init JAX.
"""

import json
import subprocess
import sys

import pytest

from deeplearning4j_tpu.native import pjrt

_CHILD = r"""
import json

import numpy as np

from deeplearning4j_tpu.native.pjrt import PjrtSmoke, PjrtSmokeError

out = {}
s = PjrtSmoke().open()
out["api_version"] = s.api_version()
try:
    s.create_client()
    out["platform"] = s.platform_name()
    out["devices"] = s.device_count()
    x = np.arange(16, dtype=np.float32)
    out["roundtrip_ok"] = bool(np.allclose(s.roundtrip(x), x))
    out["add_ok"] = bool(np.allclose(s.execute_add(x, 2 * x), 3 * x))
    s.close()
except PjrtSmokeError as e:
    out["client_error"] = str(e)[:200]
print("RESULT " + json.dumps(out))
"""


@pytest.mark.skipif(not pjrt.buildable(), reason="g++ or pjrt_c_api.h unavailable")
@pytest.mark.skipif(pjrt.default_plugin_path() is None, reason="no PJRT plugin .so")
def test_pjrt_c_abi_smoke():
    import os

    env = dict(os.environ)
    # the child must see the real environment (libtpu init consults TPU_*/
    # metadata vars; a stripped env makes it probe the network and hang) but
    # must NOT inherit a forced-CPU JAX setting from the test session
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                          text=True, timeout=180, env=env)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, f"child failed:\n{proc.stdout}\n{proc.stderr[-2000:]}"
    res = json.loads(lines[0][len("RESULT "):])
    # the ABI surface itself must always work: load + version negotiation
    major, minor = res["api_version"]
    assert major >= 0 and minor > 0
    if "client_error" in res:
        # no locally-attached accelerator: the plugin must have failed with
        # its own initialization error, not an ABI-level crash
        assert "client_create" in res["client_error"]
        pytest.skip(f"no local PJRT device: {res['client_error']}")
    # hardware present: the full C-only path must produce correct numerics
    assert res["devices"] >= 1
    assert res["roundtrip_ok"] and res["add_ok"]
