"""config-drift clean fixture: every knob read is registered and
documented, and is set by a harness or is a deployment setting."""
import os

GOOD = os.environ.get("NOMAD_TPU_GOOD_KNOB", "1")
PATH = os.environ.get("NOMAD_TPU_PATH_KNOB", "auto")
CHILD_ENV = {"NOMAD_TPU_GOOD_KNOB": "0"}
