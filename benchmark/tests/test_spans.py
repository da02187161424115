from harness.spans import parse_server_timing


def test_program_header():
    h = ("fetch;dur=0.31, probe;dur=0.40, decode;dur=3.68, batch_form;dur=5.00, "
         "dispatch_wait;dur=0.17, drain;dur=7.53, execute;dur=317.04, encode;dur=0.33, "
         "total;dur=321.73")
    st = parse_server_timing(h)
    assert st["decode"] == 3.68
    assert st["total"] == 321.73
    assert list(st) == ["fetch", "probe", "decode", "batch_form", "dispatch_wait",
                        "drain", "execute", "encode", "total"]


def test_repeats_sum_and_junk_is_skipped():
    st = parse_server_timing('a;dur=1.5, a;dur=2, b;desc="x", c;dur=bad, ;dur=3, d;dur="4"')
    assert st == {"a": 3.5, "d": 4.0}


def test_missing_header():
    assert parse_server_timing(None) == {}
    assert parse_server_timing("") == {}
