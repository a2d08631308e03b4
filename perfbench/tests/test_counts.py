"""The analytic counts against a hand count at Table-4 widths."""
import json

import counts as K
import population as P
from manifest import HERE

CONF = json.load(open(HERE / "configs" / "table4-silo32.json"))
HEAD = P.mlp_dims(CONF, 4)["heads"]


def test_head_widths_are_table4():
    assert HEAD == (3, 16, 256, 64, 16, 1)
    assert P.mlp_dims(CONF, 4)["embed"] == (12, 16, 256, 64, 16, 3)
    assert P.mlp_dims(CONF, 4)["pred"] == (7, 32, 256, 16, 1, 1)


def test_eq7_flops_per_triple():
    # 3*16*2+16 + 16*256*2+256 + 256*64*2+64 + 64*16*2+16 + 16*2+1
    assert K.head_forward_flops(HEAD) == 43_489
    assert K.eq7_flops(1, 1, 1, HEAD) == 43_492


def test_head_params():
    assert K.head_params(HEAD) == 21_921


def test_client_step_at_c32():
    # one client's sweep: 4 features x 128 pool rows x 50 probes
    assert round(K.eq7_flops(128, 4, 50, HEAD) / 1e9, 3) == 1.113
    assert round(K.eq7_bytes(128, 4, 50, HEAD) / 1e6, 2) == 11.23


def test_forward_flops_per_sample():
    # heads 4 x 43,136 + embed 43,488 + pred 25,058 matmul FLOPs
    assert K.forward_flops(P.mlp_dims(CONF, 4), 4) == 241_090
