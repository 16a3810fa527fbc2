"""bench/ops.py against a hand count."""
import harness
import ops
from conftest import BENCH


def test_int8_ops_hand_count_smollm():
    conf = harness.load_json(BENCH / "configs" / "smollm-135m.int8.json")
    # one layer: q 576x576, k and v 576x192 each, o 576x576, gate and up
    # 576x1536 each, down 1536x576
    layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert layer == 3_538_944
    head = 576 * 49152
    assert ops.projection_weights(conf) == 30 * layer + head == 134_479_872
    assert ops.int8_ops_per_token(conf) == 2 * 134_479_872


def test_ops_do_not_depend_on_backend():
    a = harness.load_json(BENCH / "configs" / "smollm-135m.int8.json")
    b = harness.load_json(BENCH / "configs" / "smollm-135m.approx.json")
    assert ops.int8_ops_per_token(a) == ops.int8_ops_per_token(b)
