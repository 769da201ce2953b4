"""The case study's numeric int8 stage on a CPU: the convcore and
postproc Pallas kernels in interpret mode against the jnp reference."""
import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_numeric_int8_stage_in_interpret_mode_matches_the_reference():
    spec = importlib.util.spec_from_file_location(
        "yolov3_soc_sim", os.path.join(REPO, "examples", "yolov3_soc_sim.py"))
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    errs = example.numeric_int8_stage(interpret=True)
    assert len(errs) == 2
    assert all(err <= 1e-5 for err in errs), errs
