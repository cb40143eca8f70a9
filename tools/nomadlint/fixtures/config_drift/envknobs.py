"""config-drift fixture registry."""
DEPLOYMENT_KNOBS = frozenset({"NOMAD_TPU_PATH_KNOB"})

ENV_KNOBS = {
    "NOMAD_TPU_GOOD_KNOB": ("1", "fixture.py", "a documented knob"),
    "NOMAD_TPU_PATH_KNOB": (
        "auto", "fixture.py", "a deployment setting nothing sets",
    ),
}
