"""Writes `workflows/longdoc-txt2img-glm-5.2.json` and its byte copy under
`benchmark/workflows/`: K-EXAONE's rewrite graph with `glm-5.2-ep16-5l`, 128
new tokens, one draft a step, and a 32,767-byte text: the cells' 8,191-byte
style guide byte for byte, then a 24,576-byte manuscript, a story's chapter
told scene by scene, ending in the line that asks for one scene's prompt.
And `longdoc-txt2img-granite-4.0-h-micro.json` beside it (`DOCUMENTS`):
the same graph with `granite-4.0-h-micro`, no draft, and a 65,535-byte
text, the guide and a 57,344-byte manuscript from another seed. And
`longdoc-txt2img-dots3-note.json`: the first file's text, byte for byte,
with `dots3-note-prev-ep8-5l`, no draft and 256 new tokens. And
`longdoc-txt2img-longcat-flash.json`: the same text once more, with
`longcat-flash-chat-ep64-4l`, no draft and 128 new tokens.

The manuscript is original prose put together from the phrase lists below
by a seeded generator (no network, no corpus): `python3
scripts/gen_longdoc_workflow.py` writes the same bytes every time, and
the workflow holds the literal text. `tests/test_lm_served_path.py` holds
the lengths.
"""

from __future__ import annotations

import json
import os
import random

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOURCE = os.path.join(ROOT, "workflows", "rewrite-txt2img-k-exaone.json")
NAME = "longdoc-txt2img-glm-5.2.json"
MANUSCRIPT_BYTES = 24576
# file name: (checkpoint, the generator's seed, the manuscript's bytes, drafts a step,
# new tokens)
DOCUMENTS = {
    NAME: ("glm-5.2-ep16-5l", 52, MANUSCRIPT_BYTES, 1, 128),
    "longdoc-txt2img-granite-4.0-h-micro.json": ("granite-4.0-h-micro", 54, 57344, 0, 128),
    "longdoc-txt2img-dots3-note.json": ("dots3-note-prev-ep8-5l", 52, MANUSCRIPT_BYTES, 0, 256),
    "longdoc-txt2img-longcat-flash.json": (
        "longcat-flash-chat-ep64-4l", 52, MANUSCRIPT_BYTES, 0, 128),
}
ASK = "\n\nIllustrate scene {scene} of the chapter above, and nothing of the other scenes.\nPrompt:"

PEOPLE = ["Marit", "the ferryman", "old Tobias", "the lamplighter's daughter", "Ilse",
          "the surveyor", "a boy from the salt works", "the widow Ansgar", "Jorun",
          "the clockmaker", "two sisters from the upper farm", "the harbour pilot"]
PLACES = ["the quay below the customs house", "a kitchen with a blue tiled stove",
          "the ridge path above the tree line", "the drying loft over the boat shed",
          "a courtyard where the well had frozen", "the chapel porch",
          "the long room of the inn", "a rowing boat in the middle of the fjord",
          "the orchard behind the school", "the signal hut at the pass",
          "the market square before the stalls were up", "the mill race"]
LIGHTS = ["under a low grey sky", "in the first yellow light", "by a single oil lamp",
          "while snow came sideways off the water", "in the long blue dusk",
          "at noon, with no shadow anywhere", "under a moon two days from full",
          "as the fog lifted in strips", "in rain that had not stopped since Sunday",
          "with the sun already behind the western wall"]
OBJECTS = ["a coil of tarred rope", "the brass sextant", "a basket of winter apples",
           "the ledger with the torn spine", "a lantern with one cracked pane",
           "her mother's grey shawl", "a sack of seed barley", "the unfinished letter",
           "a pair of skates tied by their laces", "the red signal flag",
           "a jar of cloudberry jam", "the key to the lower gate"]
ACTS = ["set {o} down on the step and did not pick it up again",
        "counted the boats twice and came to a different number each time",
        "asked whether the road over the pass was open, and was told to ask again in March",
        "carried {o} the whole length of the street without meeting anyone",
        "stood so long at the window that the tea went cold",
        "wrote three lines, crossed out two, and folded the page into a coat pocket",
        "found {o} where nobody had thought to look, behind the flour bin",
        "laughed for the first time since the thaw, and then looked ashamed of it",
        "mended the net by feel, eyes on the far shore",
        "said nothing, which everyone in the room understood as an answer",
        "traded {o} for a place on the morning boat",
        "walked out onto the ice as far as the first marker and turned back"]
AFTER = ["Nobody spoke of it afterwards.", "The dog watched from under the bench.",
         "Somewhere a door banged twice.", "It was the last mild day of the year.",
         "The bell at the works rang the half hour.", "Smoke stood straight up from every chimney.",
         "The tide was further out than anyone remembered.", "By evening the whole village knew.",
         "The gulls had gone inland, which meant weather.", "A kettle was already on."]


def manuscript(seed: int = 52, size: int = MANUSCRIPT_BYTES) -> str:
    rng = random.Random(seed)
    text = "\n\nManuscript, chapter nine: The Winter Crossing.\n"
    scene = 0
    while True:
        scene += 1
        who, other = rng.sample(PEOPLE, 2)
        lines = [f"\nScene {scene}. {rng.choice(PLACES).capitalize()}, {rng.choice(LIGHTS)}."]
        for _ in range(rng.randint(3, 5)):
            act = rng.choice(ACTS).format(o=rng.choice(OBJECTS))
            lines.append(f" {rng.choice([who, other]).capitalize()} {act}. {rng.choice(AFTER)}")
        piece = "".join(lines) + "\n"
        ask = ASK.format(scene=max(scene // 2, 1))
        if len(text) + len(piece) + len(ask) > size:
            ask = ASK.format(scene=max((scene - 1) // 2, 1))
            room = size - len(ask)
            text += "\nThe chapter ends here, with the lake shut and the road not yet open."
            while len(text) < room:  # short closing sentences, then spaces, up to the byte
                more = " " + rng.choice(AFTER)
                text += more if len(text) + len(more) <= room else " "
            return text + ask
        text += piece


def main() -> None:
    for name, (checkpoint, seed, size, drafts, new_tokens) in DOCUMENTS.items():
        with open(SOURCE, encoding="utf-8") as fh:
            graph = json.load(fh)
        for node in graph.values():
            inputs = node["inputs"]
            if node["class_type"] == "CheckpointLoaderSimple":
                inputs["ckpt_name"] = checkpoint
            elif node["class_type"] == "TextGenerate":
                guide = inputs["text"]
                assert len(guide.encode()) == 8191, len(guide.encode())
                body = manuscript(seed, size)
                assert body.isascii() and len(body) == size, len(body)
                inputs.update(text=guide + body, max_new_tokens=new_tokens, draft_tokens=drafts)
            elif node["class_type"] == "SaveImage":
                inputs["filename_prefix"] = name[: -len(".json")]
        data = json.dumps(graph, indent=2) + "\n"
        for folder in ("workflows", os.path.join("benchmark", "workflows")):
            with open(os.path.join(ROOT, folder, name), "w", encoding="utf-8") as fh:
                fh.write(data)


if __name__ == "__main__":
    main()
