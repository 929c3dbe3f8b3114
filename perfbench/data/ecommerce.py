"""SemBench-style e-commerce schema, vectorised.

Same tables, columns, distributions and latent truths as the program's
per-row ``make_ecommerce``: at scale 1, 600 products and 1,800 reviews.
"""
from __future__ import annotations

import numpy as np

from . import Data, money, pick, rng_for

PROMPTS = {
    "PRODUCT_IS_ELECTRONICS": ("Is this product an electronics item? "
                               "{products.description}. Answer YES or NO."),
    "PRODUCT_ECO": ("Is this product marketed as eco-friendly? "
                    "{products.description}. Answer YES or NO."),
    "PRODUCT_FOR_KIDS": ("Is this product suitable for children? "
                         "{products.description}. Answer YES or NO."),
    "ECOM_REVIEW_POSITIVE": ("Is this product review positive? "
                             "{previews.text}. Answer YES or NO."),
    "ECOM_REVIEW_DEFECT": ("Does the review report a defect? "
                           "{previews.text}. Answer YES or NO."),
}

LATENT = {
    "PRODUCT_IS_ELECTRONICS": (("products",),
                               lambda p: p["_cat"] == "electronics"),
    "PRODUCT_ECO": (("products",), lambda p: p["_eco"]),
    "PRODUCT_FOR_KIDS": (("products",), lambda p: p["_kids"]),
    "ECOM_REVIEW_POSITIVE": (("previews",), lambda r: r["_sentiment"] > 0),
    "ECOM_REVIEW_DEFECT": (("previews",), lambda r: r["_defect"]),
}

CATEGORIES = ["electronics", "toys", "kitchen", "garden", "clothing"]
SENT_WORDS = {2: ("fantastic", "loved"), 1: ("good", "enjoyed"),
              0: ("okay", "fine"), -1: ("weak", "disliked"),
              -2: ("awful", "hated")}


def _truths() -> dict:
    p = PROMPTS
    return {
        p["PRODUCT_IS_ELECTRONICS"]:
            lambda c: c["products"]["_cat"] == "electronics",
        p["PRODUCT_ECO"]: lambda c: c["products"]["_eco"],
        p["PRODUCT_FOR_KIDS"]: lambda c: c["products"]["_kids"],
        p["ECOM_REVIEW_POSITIVE"]: lambda c: c["previews"]["_sentiment"] > 0,
        p["ECOM_REVIEW_DEFECT"]: lambda c: c["previews"]["_defect"],
    }


def generate(seed: int, scale: float) -> Data:
    """Products and reviews at ``scale`` from ``seed``."""
    rng = rng_for(seed)
    n_prod, n_rev = int(600 * scale), int(1800 * scale)
    cat = pick(rng, CATEGORIES, n_prod)
    eco = rng.random(n_prod) < 0.2
    kids = (cat == "toys") | (rng.random(n_prod) < 0.1)
    quality = rng.integers(1, 6, size=n_prod)
    desc = np.asarray(
        [f"A {c} item, model {i}, build grade {q}."
         + (" Made from recycled materials." if e else "")
         + (" Safe for ages 3 and up." if k else "")
         for i, (c, q, e, k) in enumerate(zip(cat.tolist(), quality.tolist(),
                                              eco.tolist(), kids.tolist()))],
        dtype=object)
    products = {"product_id": np.arange(n_prod),
                "title": np.asarray([f"Product {i}" for i in range(n_prod)],
                                    dtype=object),
                "category": cat,
                "price": money(rng, 5, 500, n_prod),
                "brand": np.asarray([f"brand{i % 40}" for i in range(n_prod)],
                                    dtype=object),
                "description": desc,
                "_cat": cat, "_eco": eco, "_kids": kids, "_quality": quality}
    sent = rng.integers(-2, 3, size=n_rev)
    defect = rng.random(n_rev) < 0.15
    word = rng.integers(2, size=n_rev)
    text = np.asarray(
        [f"Purchase {i} felt {SENT_WORDS[s][w]}."
         + (" It broke after two days, clearly defective." if d else "")
         for i, (s, w, d) in enumerate(zip(sent.tolist(), word.tolist(),
                                           defect.tolist()))], dtype=object)
    previews = {"review_id": np.arange(n_rev),
                "product_id": rng.integers(int(n_prod * 1.2), size=n_rev),
                "text": text,
                "rating": np.clip(sent + 3, 1, 5),
                "_sentiment": sent, "_defect": defect}
    return Data(tables={"products": products, "previews": previews},
                text={"products": {"title", "category", "brand",
                                   "description"},
                      "previews": {"text"}},
                prompts=dict(PROMPTS), truths=_truths())
