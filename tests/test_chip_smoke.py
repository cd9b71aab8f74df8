"""chip_smoke.py on the CPU: it must refuse to run, and the kernel shapes
it compiles on the chip must be the ones ResNet-50 makes at batch 128."""
import importlib.util
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_SMOKE = os.path.join(ROOT, 'chip_smoke.py')


def test_refuses_the_cpu_before_building_a_model():
    proc = subprocess.run(
        [sys.executable, CHIP_SMOKE],
        env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    # the device line comes first, and the refusal names the platform
    assert proc.stdout.startswith('platform=cpu device_kind=cpu count=')
    assert "platform='cpu'" in proc.stderr
    assert '[train] start' not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_kernel_shapes_are_resnet50s():
    spec = importlib.util.spec_from_file_location('chip_smoke', CHIP_SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    convs = smoke.conv_shapes()
    # (kernel, height, in channels, filters, stride)
    assert [c[1:] for c in convs if c[0] == 3] == [
        (7, 512, 512, 1), (14, 256, 256, 1), (14, 512, 512, 2),
        (28, 128, 128, 1), (28, 256, 256, 2), (56, 64, 64, 1),
        (56, 128, 128, 2)]
    ones = [c[1:] for c in convs if c[0] == 1]
    assert (56, 64, 64, 1) in ones and (7, 2048, 512, 1) in ones
    assert all(stride in (1, 2) for *_, stride in ones)
