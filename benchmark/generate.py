"""The one general traffic generator: every mix is a data file of
parameters under ``benchmark/traffic/`` that names one of the kinds
below.  A later PR adds a mix by adding a file, not code.

Copied, with the changes listed, from ``chip_smoke.py`` (PR 21):
``dedup_corpus`` → :func:`news_corpus`, ``entity_universe`` →
:func:`entity_universe`, ``matcher_articles`` → :func:`news_articles`.
Changes: every size is a parameter of the mix file, and the randomness
is split in two.  The *shape* (lengths, which articles are copies or
mutants and their target Jaccard, word counts, mention counts, typo
flags) comes from the mix's fixed ``shape_seed`` and is the same for
every item of the pool; the *content* (bytes, words, which names,
positions) and the order come from ``--seed`` and the item's index.  So
every seed, and every item, does the same amount of work in another
order, and a window that ends one item earlier or later times the same
kind of work.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def sub_seed(*parts: int) -> int:
    """A 32-bit seed from any number of whole numbers (``--seed`` may
    exceed 32 bits; ``RandomState`` takes at most 2**32 - 1)."""
    return int(np.random.SeedSequence([int(p) & ((1 << 64) - 1) for p in parts])
               .generate_state(1)[0])


# -- dedup corpora -----------------------------------------------------------


@dataclass
class Corpus:
    docs: list            # list[bytes]
    family: np.ndarray    # int64[n]: the base article each one descends from
    pairs: list           # [(src, dup, "exact" | "near")] planted pairs


def _mutate_to_jaccard(rng, text: bytes, target_j: float) -> bytes:
    """Replace one random span so the k-shingle Jaccard with ``text`` lands
    near ``target_j`` (the span share f = (1-j)/(1+j)); same construction
    as ``cpu.oracle.mutate_to_jaccard``, written here so the traffic
    imports nothing of the program."""
    f = (1.0 - target_j) / (1.0 + target_j)
    span = max(1, int(len(text) * f))
    pos = rng.randint(0, max(1, len(text) - span))
    b = bytearray(text)
    b[pos : pos + span] = rng.randint(32, 127, size=span, dtype=np.uint8).tobytes()
    return bytes(b)


def news_corpus(mix: dict, seed: int, index: int) -> Corpus:
    """Corpus ``index`` of the pool: ``mix["articles"]`` ragged articles of
    random printable bytes with planted exact copies and mutants."""
    n = int(mix["articles"])
    shape = np.random.RandomState(sub_seed(mix["shape_seed"]))
    content = np.random.RandomState(sub_seed(seed, index))
    u = shape.rand(n)
    mu, sigma = mix["body_lognormal"]
    lo, hi = mix["body_clip"]
    lens = np.clip(shape.lognormal(mu, sigma, size=n), lo, hi).astype(np.int64)
    mid = u > 1.0 - mix["mid_share"] - mix["long_share"]
    lens[mid] = shape.randint(*mix["mid_bytes"], size=int(mid.sum()))
    long = u > 1.0 - mix["long_share"]
    lens[long] = shape.randint(*mix["long_bytes"], size=int(long.sum()))
    kind = shape.rand(n)
    copy_share = mix["copy_share"]
    plant_share = copy_share + mix["mutant_share"]
    docs: list[bytes] = []
    family = np.arange(n)
    bases: list[int] = []
    pairs: list[tuple[int, int, str]] = []
    for i in range(n):
        if i >= 8 and kind[i] < copy_share:
            src = int(shape.randint(0, i))
            docs.append(docs[src])
            family[i] = family[src]
            pairs.append((src, i, "exact"))
        elif i >= 8 and kind[i] < plant_share and bases:
            src = bases[int(shape.randint(0, len(bases)))]
            jlo, jhi = mix["knee_j"] if shape.rand() < mix["knee_share"] else mix["easy_j"]
            docs.append(_mutate_to_jaccard(content, docs[src], shape.uniform(jlo, jhi)))
            family[i] = family[src]
            pairs.append((src, i, "near"))
        else:
            docs.append(content.randint(32, 127, size=int(lens[i]), dtype=np.uint8).tobytes())
            bases.append(i)
    # the seed also sets the order: same work, another arrival order
    perm = content.permutation(n)
    where = np.empty(n, np.int64)
    where[perm] = np.arange(n)
    return Corpus(
        docs=[docs[int(p)] for p in perm],
        family=where[family[perm]],
        pairs=[(int(where[s]), int(where[d]), k) for s, d, k in pairs],
    )


# -- entity universe and articles (the matcher) --------------------------------

_SYL = ("ka", "lo", "mi", "ne", "ru", "ta", "vo", "zi", "ber", "dan", "fel",
        "gor", "hal", "jun", "kor", "lam", "mor", "nex", "pol", "quin", "ros",
        "sel", "tor", "ul", "var", "wen", "xan", "yor", "zed", "tri", "cor")

#: filler syllables apart from the names' own: an article shares few
#: q-grams with the names it does not mention
_FILLER = ("the", "and", "was", "for", "ing", "ion", "ent", "ove", "ere", "all",
           "out", "day", "ear", "ith", "eek", "ous", "ight", "ame", "ice", "ers")


def _word(rng, lo: int = 2, hi: int = 4, syl=_SYL) -> str:
    return "".join(syl[int(i)] for i in rng.randint(0, len(syl), rng.randint(lo, hi)))


def _iso(rng, y0: int = 2004, y1: int = 2025) -> str:
    return (f"{int(rng.randint(y0, y1))}-{int(rng.randint(1, 13)):02d}-"
            f"{int(rng.randint(1, 29)):02d}T00:00:00Z")


def entity_universe(seed: int, n_tickers: int) -> list[dict]:
    """Company records shaped like the S&P 500 Wikidata snapshots
    (``ticker_symbol_query.py``): ``ticker``, ``country`` and the name
    attributes, some names windowed ``(Start: …) (End: …)``; one company
    in 50 is non-US (the loader drops it)."""
    rng = np.random.RandomState(sub_seed(seed))
    seen: set[str] = set()
    out = []
    for t in range(n_tickers):
        while True:
            tick = "".join(chr(65 + int(c)) for c in rng.randint(0, 26, rng.randint(2, 5)))
            if tick not in seen:
                seen.add(tick)
                break
        base = _word(rng).capitalize() + (" " + _word(rng).capitalize() if rng.rand() < 0.5 else "")
        suffix = ("Inc.", "Corp", "Holdings", "Group", "Co")[t % 5]
        acro = "".join(w[0] for w in base.split()).upper() + tick[:2]

        def person():
            return f"{_word(rng, 1, 3).capitalize()} {_word(rng).capitalize()}"

        def windowed(name):
            r = rng.rand()
            if r < 0.3:
                return f"{name} (Start: {_iso(rng, 2004, 2014)})"
            if r < 0.45:
                return f"{name} (Start: {_iso(rng, 2004, 2010)}) (End: {_iso(rng, 2012, 2020)})"
            return name

        out.append({
            "id_label": f"{base} {suffix}",
            "ticker": tick,
            "country": ["Germany"] if t % 50 == 7 else ["United States"],
            "aliases": [tick, base, acro],
            "products": [f"{_word(rng).capitalize()} Pro", _word(rng).lower()],
            "subsidiaries": [windowed(f"{_word(rng).capitalize()} Labs")],
            "owned_entities": [],
            "ceos": [windowed(person()), windowed(person())],
            "board_members": [person()],
        })
    return out


def universe_names(companies: list[dict]) -> list[str]:
    """Every plain name of the universe, windows stripped (what articles
    mention)."""
    names = []
    for c in companies:
        for attr in ("id_label", "aliases", "products", "subsidiaries", "ceos", "board_members"):
            vals = c[attr] if isinstance(c[attr], list) else [c[attr]]
            names.extend(v.split(" (Start:")[0] for v in vals)
    return names


def news_articles(mix: dict, seed: int, index: int, names: list[str]) -> list[dict]:
    """Chunk ``index`` of the pool: ``mix["articles"]`` rows in the
    reference dataset schema with planted mentions (exact names,
    one-letter typos, names in titles)."""
    n = int(mix["articles"])
    shape = np.random.RandomState(sub_seed(mix["shape_seed"]))
    content = np.random.RandomState(sub_seed(seed, index))
    vocab_rng = np.random.RandomState(sub_seed(mix["shape_seed"], 1))
    vocab = [_word(vocab_rng, 1, 4, _FILLER) for _ in range(int(mix["vocab"]))]
    mu, sigma = mix["words_lognormal"]
    wlo, whi = mix["words_clip"]
    mlo, mhi = mix["mentions"]
    tlo, thi = mix["title_words"]
    rows = []
    for i in range(n):
        nw = int(np.clip(shape.lognormal(mu, sigma), wlo, whi))
        words = [vocab[int(j)] for j in content.randint(0, len(vocab), nw)]
        for _ in range(int(shape.randint(mlo, mhi + 1))):
            name = names[int(content.randint(0, len(names)))]
            if shape.rand() < mix["typo_share"] and len(name) > 6:
                p = int(content.randint(1, len(name) - 1))
                name = name[:p] + chr(97 + int(content.randint(0, 26))) + name[p + 1 :]
            words.insert(int(content.randint(0, len(words) + 1)), name)
        title_words = [vocab[int(j)] for j in content.randint(0, len(vocab), shape.randint(tlo, thi + 1))]
        if shape.rand() < mix["title_mention_share"]:
            title_words.insert(0, names[int(content.randint(0, len(names)))])
        rows.append({
            "time_unix": i,
            "date_time": _iso(content),
            "title": " ".join(title_words).capitalize(),
            "url": f"https://news.example/{index}/{i}.html",
            "source": "bench",
            "source_url": "https://news.example",
            "article_text": " ".join(words),
        })
    order = content.permutation(n)
    return [rows[int(k)] for k in order]
