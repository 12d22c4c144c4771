"""Test bootstrap: make ``repro`` (src layout) importable regardless of
how pytest is invoked, and isolate the calibration store so dispatch
predictions never depend on whatever ``~/.cache/repro/calibrations``
happens to hold on the host."""
import os
import sys
import tempfile
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

os.environ["REPRO_CALIBRATION_DIR"] = tempfile.mkdtemp(
    prefix="repro-cal-test-")

# Hypothesis profiles (no-op on stripped hosts where only the stub in
# tests/_hypothesis_stub.py is available): "default" keeps tier-1 fast;
# "nightly" is the CI fuzz lane's budget, selected with
# ``--hypothesis-profile=nightly`` (falsifying examples persist under
# .hypothesis/ and are uploaded as artifacts by the workflow).
try:
    from hypothesis import HealthCheck, settings as _hyp_settings
except ImportError:
    pass
else:
    _hyp_settings.register_profile("default", max_examples=25,
                                   deadline=None)
    _hyp_settings.register_profile(
        "nightly", max_examples=400, deadline=None,
        print_blob=True,
        suppress_health_check=[HealthCheck.too_slow])
    _hyp_settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "default"))
