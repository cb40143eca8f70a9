"""config-drift bad fixture: reads a knob the registry and docs
don't know, and nothing sets the one they do."""
import os

# BAD: registered, but no test or harness ever sets it
GOOD = os.environ.get("NOMAD_TPU_GOOD_KNOB", "1")
# BAD: unregistered, undocumented
ROGUE = os.environ.get("NOMAD_TPU_ROGUE_KNOB", "0")
