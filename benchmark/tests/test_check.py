"""What ``correct`` lets through and what it does not, on scripted
replies (no server): the rule, not the arithmetic of the model."""

import run as bench_run

CFG = {"vocab_size": 100,
       "correct": {"limit": 0.08, "limit_p75": 0.15, "limit_request": 0.7,
                   "first_len": 8,
                   "tail_len": 4, "other_lens": [3, 5], "new_tokens": 12}}


def _scripted(monkeypatch, served_error, tokens_of=None):
    """``client.checked`` answers ``generate`` with 12 tokens whose
    logprobs are off the reference's (all -1.0) by ``served_error(key,
    position)``; requests are told apart by their prompt."""
    keys = {}

    def checked(addr, obj, timeout=0):
        if obj["op"] == "reference":
            return {"logprobs": [-1.0] * len(obj["served"])}
        prompt = tuple(obj["prompt"])
        n = keys.setdefault(prompt, 0)
        keys[prompt] += 1
        # the first prompt comes twice: alone, then as the cached turn
        key = ("first" if n == 0 else "cached_again") \
            if len(prompt) == 8 else f"len{len(prompt)}"
        toks = (tokens_of or (lambda k: list(range(12))))(key)
        return {"tokens": toks,
                "logprobs": [-1.0 + served_error(key, i) for i in range(12)]}

    monkeypatch.setattr(bench_run.client, "checked", checked)
    monkeypatch.setattr(bench_run, "in_parallel",
                        lambda fn, items: [fn(x) for x in items])
    return bench_run.check_correct({"addr": "a", "ctl": "c"}, CFG, 7)


def test_a_sound_run_with_a_few_flipped_positions_is_correct(monkeypatch):
    assert _scripted(monkeypatch, lambda k, i: 3.0 if i == 5 else 0.03)


def test_one_request_served_from_wrong_pages_is_not_correct(monkeypatch):
    # 12 of 60 positions, three of them right by chance: the median and the
    # 75th percentile over all stand, the request's own median does not
    assert not _scripted(monkeypatch, lambda k, i: (
        2.0 if k == "cached_again" and i % 4 else 0.01))


def test_a_third_of_all_positions_wrong_is_not_correct(monkeypatch):
    # every request's median and the median over all stand; the quartile not
    assert not _scripted(monkeypatch, lambda k, i: 0.6 if i % 3 == 0 else 0.0)


def test_a_small_error_everywhere_is_not_correct(monkeypatch):
    assert not _scripted(monkeypatch, lambda k, i: 0.1)


def test_a_truncated_reply_is_not_correct(monkeypatch):
    assert not _scripted(monkeypatch, lambda k, i: 0.0,
                         tokens_of=lambda k: list(range(12 if k != "len5"
                                                        else 7)))
