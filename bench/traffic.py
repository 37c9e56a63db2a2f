"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns (mix, rate, seconds, seed) into an
open-loop schedule of OpenAI chat requests.

Every seed gets the same work: one sequence of inter-arrival gaps, question
lengths, ``max_tokens`` and repeat flags is drawn once from a fixed stream,
and the seed only rotates it (starts it at another request) and writes the
text.  So two seeds differ in where the sequence starts and in wording, not
in how much there is to do or in how requests overlap, which is what the
tails of a lightly loaded server depend on.

The topic list and question templates are copied from the program's
planted workload (``core/workload.py``) so that the yardstick does not move
when the program does.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

TOPICS = [
    "health", "nutrition", "religion", "history", "sports", "cricket",
    "politics", "education", "technology", "finance", "travel", "weather",
    "cooking", "culture", "language", "science", "medicine", "agriculture",
    "jobs", "entertainment",
]

TEMPLATES = [
    "tell me about {}", "what is {}", "how does {} work", "why is {} important",
    "give me advice on {}", "explain {} simply", "what are the benefits of {}",
    "history of {}", "latest news about {}", "how to improve {}",
]

# words that make a question specific; each question draws its own
DETAIL = (
    "rain season village market price school exam fever cough water crop "
    "seed loan bank phone battery signal bus train ticket visa passport "
    "recipe rice lentil spice festival prayer match score team player goal "
    "vote election law court tax salary interview resume clinic vaccine "
    "doctor medicine dose child mother elder river flood drought harvest "
    "cattle milk solar power wind road bridge library teacher lesson "
    "homework grammar word poem song film actor news radio internet "
    "computer memory cache process thread kernel page table disk file "
    "network packet router protocol compiler loop function variable "
    "pointer array matrix vector proof theorem limit integral series"
).split()

# questions the service has not seen: their own templates and words, so a
# new question does not land on a cached one by the words it shares
NOVEL_TEMPLATES = [
    "where can I find {}", "who decides {}", "when will {} open",
    "which {} should I choose", "can you list {}", "is there {} nearby",
    "whom do I call for {}", "could someone fix {}",
]
NOVEL_WORDS = (
    "umbrella ferry lantern plumber tailor bicycle pharmacy kiosk mosque "
    "temple stadium embassy notary locksmith carpenter glacier volcano "
    "orchard vineyard hostel airport harbour lighthouse canal tunnel "
    "subway tram scooter garage bakery butcher florist jeweller optician "
    "dentist midwife nursery kindergarten orphanage pension lottery "
    "passport renewal certificate notarized deed mortgage insurance "
    "warranty refund receipt invoice courier parcel stamp envelope "
    "mailbox generator inverter borehole pump tank gutter roof tile brick "
    "cement plaster paint varnish ladder hammer chisel drill sewing "
    "needle thread fabric cotton wool silk leather sandal boot jacket "
    "sweater scarf glove blanket pillow mattress curtain carpet sofa "
    "stool shelf drawer cupboard fridge kettle blender toaster oven "
    "stove chimney fireplace candle matchbox torch lamp bulb socket "
    "switch cable antenna satellite dish modem printer scanner"
).split()

STUDENTS = ("ana ben chen dara eli femi gita hana ivan joy kai lena mo nia "
            "omar priya quinn ravi sara tom uma vik wen xia yusuf zoe").split()

COURSES = [
    ("CS101", "Introduction to Computer Systems",
     ["memory", "cache", "process", "thread", "kernel", "page", "table",
      "disk", "file", "network", "packet", "compiler", "loop", "pointer"]),
    ("PH110", "Foundations of Public Health",
     ["health", "vaccine", "clinic", "fever", "water", "nutrition", "child",
      "mother", "elder", "medicine", "dose", "doctor", "flood", "drought"]),
    ("HI205", "World History since 1800",
     ["history", "election", "law", "court", "festival", "river", "bridge",
      "road", "train", "news", "radio", "film", "culture", "language"]),
]


def _fit(words: List[str], n_bytes: int) -> str:
    """Join words into exactly ``n_bytes`` ASCII bytes ending in '?'."""
    text = ""
    for w in words:
        nxt = w if not text else text + " " + w
        if len(nxt) + 1 > n_bytes:
            break
        text = nxt
    text = text + "?"
    while len(text) < n_bytes:          # short: pad with the last letters
        text = text[:-1] + "." * (n_bytes - len(text)) + "?"
    return text[:n_bytes]


def question(rng: np.random.Generator, n_bytes: int, topic_words=None,
             lead: str = "", novel: bool = False) -> str:
    """A question of exactly ``n_bytes``; ``novel`` draws it from the
    templates and words the past questions never use."""
    templates, detail = (NOVEL_TEMPLATES, NOVEL_WORDS) if novel else \
        (TEMPLATES, DETAIL)
    if topic_words is not None:
        topic = str(rng.choice(topic_words))
    elif novel:
        topic = NOVEL_WORDS[int(rng.integers(len(NOVEL_WORDS)))]
    else:
        topic = TOPICS[int(rng.integers(len(TOPICS)))]
    head = templates[int(rng.integers(len(templates)))].format(topic)
    words = ([lead] if lead else []) + head.split()
    while len(" ".join(words)) < n_bytes + 16:
        words.append(detail[int(rng.integers(len(detail)))])
    return _fit(words, n_bytes)


def system_prompt(code: str, title: str, words: List[str], n_bytes: int,
                  rng: np.random.Generator) -> str:
    parts = [f"You are the teaching assistant of {code}, {title}. Answer the "
             "student briefly, cite the lecture and week, and say when a "
             "question is outside the syllabus."]
    week = 1
    while len(" ".join(parts)) < n_bytes:
        a, b = rng.choice(words, size=2, replace=False)
        parts.append(f"Week {week} covers {a} and how it relates to {b}.")
        week += 1
    return " ".join(parts)[:n_bytes]


@dataclasses.dataclass
class Request:
    i: int
    due: float                 # seconds after the window opens
    body: Dict[str, Any]       # the JSON body of POST /v1/chat/completions
    stream: bool
    max_tokens: int
    prompt: str                # the last user message: what the engine sees
    repeat_of: Optional[int] = None   # cache object id a repeat asks again

    def wire(self) -> Dict[str, Any]:
        return {"i": self.i, "due": self.due, "stream": self.stream,
                "body": self.body}


def _fixed(mix: Dict[str, Any], n: int):
    """The seed-independent work of ``n`` requests: gaps, lengths,
    max_tokens and repeat flags, in a fixed order.  Repeats are spread
    evenly through the sequence."""
    rng = np.random.default_rng([0x5EED, n])
    gaps = rng.exponential(1.0, size=n)
    lo, hi = mix["question_bytes"]
    lengths = rng.integers(lo, hi + 1, size=n)
    mt = mix["max_tokens"]
    toks = np.exp(rng.normal(math.log(mt["median"]), mt["sigma"], size=n))
    toks = np.clip(np.rint(toks), mt["min"], mt["max"]).astype(int)
    share = mix.get("repeat_share", 0.0)
    repeats = np.zeros(n, bool)
    k = int(round(share * n))
    repeats[np.floor(np.arange(k) * n / max(k, 1)).astype(int)] = True
    return gaps, lengths, toks, repeats


def n_requests(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def cache_objects(mix: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """The past questions and answers set-up loads into the semantic cache
    (``cache_fill``), one object per past question.  Each answer starts with
    its own id and is longer than the 96 characters a cache-served answer
    shows, so the object behind every cache-served answer is known."""
    fill = mix.get("cache_fill")
    if not fill:
        return []
    rng = np.random.default_rng([seed, 11])
    lo, hi = mix["question_bytes"]
    alo, ahi = fill["answer_bytes"]
    seen, out = set(), []
    while len(out) < fill["objects"]:
        q = question(rng, int(rng.integers(lo, hi + 1)))
        if q in seen:
            continue
        seen.add(q)
        k = len(out)
        words = [f"A{k}:"] + [DETAIL[int(j)] for j in
                              rng.integers(len(DETAIL), size=80)]
        n_bytes = int(rng.integers(alo, ahi + 1))
        out.append({"id": k, "question": q, "answer": _fit(words, n_bytes)})
    return out


def zipf_ranks(n_objects: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_objects + 1) ** s
    return w / w.sum()


def schedule(mix: Dict[str, Any], rate: float, seconds: float, seed: int,
             objects: Optional[List[Dict[str, Any]]] = None) -> List[Request]:
    """Open-loop schedule of the requests due in ``[0, seconds)``."""
    n = n_requests(rate, seconds)
    gaps, lengths, toks, repeats = _fixed(mix, n)
    shift = int(np.random.default_rng([seed, 1]).integers(n))
    gaps, lengths, toks, repeats = (np.roll(a, shift) for a in
                                    (gaps, lengths, toks, repeats))
    due = np.cumsum(gaps)
    due = (due - due[0]) * (seconds * (n - 1) / n) / max(due[-1] - due[0],
                                                         1e-9)
    text_rng = np.random.default_rng([seed, 2])
    # sessions: each request continues an open session or starts one
    tmin, tmax = mix["turns"]
    sessions: List[int] = []
    sid = 0
    while len(sessions) < n:
        sessions += [sid] * int(text_rng.integers(tmin, tmax + 1))
        sid += 1
    sessions = list(text_rng.permutation(np.asarray(sessions[:n])))
    courses = mix.get("courses", 0)
    prompts = [system_prompt(code, title, words, mix["system_bytes"],
                             np.random.default_rng([seed, 3, c]))
               for c, (code, title, words) in enumerate(COURSES[:courses])]
    pop = None
    if objects:
        pop = zipf_ranks(len(objects), mix["zipf_s"])
        order = np.random.default_rng([seed, 4]).permutation(len(objects))
    seen = set(o["question"] for o in objects or [])
    out = []
    for i in range(n):
        s = int(sessions[i])
        repeat_of = None
        if repeats[i] and objects:
            repeat_of = int(order[text_rng.choice(len(objects), p=pop)])
            q = objects[repeat_of]["question"]
        else:
            topic_words = None
            lead = ""
            if prompts:
                topic_words = COURSES[s % courses][2]
                lead = STUDENTS[s % len(STUDENTS)] + f"{s}:"
            while True:
                q = question(text_rng, int(lengths[i]), topic_words, lead,
                             novel=bool(objects))
                if q not in seen:
                    break
            seen.add(q)
        messages = []
        if prompts:
            messages.append({"role": "system",
                             "content": prompts[s % courses]})
        messages.append({"role": "user", "content": q})
        stream = bool(mix["stream"])
        body = {"model": "auto", "messages": messages,
                "max_tokens": int(toks[i]), "stream": stream,
                "user": f"u{s}", "x_conversation": f"s{seed}-{s}"}
        out.append(Request(i=i, due=float(due[i]), body=body, stream=stream,
                           max_tokens=int(toks[i]), prompt=q,
                           repeat_of=repeat_of))
    return out


WARM_TOKENS = 40           # two 16-token pages past a 64-token prompt


def warmup(mix: Dict[str, Any], seed: int) -> List[Request]:
    """Requests that compile every shape the mix's engine path uses: one
    per prompt length the engine can see (the byte tokenizer gives one
    token per byte, and the engine keeps the last 64), the longest decoding
    across two page boundaries of the paged cache, which no longer answer
    adds a shape to.  They bypass the cache, so each reaches the engine.
    They ask for whole answers, which run the same device programs as
    streamed ones without feeding the front door's streaming
    time-to-first-token, which the compiles would inflate; a streaming mix
    ends with one short streamed request on the warm programs."""
    rng = np.random.default_rng([seed, 5])
    lo, hi = mix["question_bytes"]
    top = min(hi, 64)
    out = []
    for k, n_bytes in enumerate(list(range(lo, top + 1)) +
                                ([top] if mix["stream"] else [])):
        q = question(rng, n_bytes, lead="warmup")
        stream = bool(mix["stream"]) and k == top + 1 - lo
        mt = WARM_TOKENS if n_bytes == top and not stream else 2
        body = {"model": "auto", "messages": [{"role": "user", "content": q}],
                "max_tokens": mt, "stream": stream,
                "user": "warmup", "x_conversation": f"warmup-{k}",
                "x_allow_cache": False}
        out.append(Request(i=-1 - k, due=0.0, body=body, stream=stream,
                           max_tokens=mt, prompt=q))
    return out
