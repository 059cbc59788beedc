"""The schedule and the pictures repeat exactly for a seed, every seed gets
the same work in another order, and a stamped picture decodes to the same
pixels in bytes no other request has."""

import io

import numpy as np

from perfbench import pictures, traffic

BIG_SEED = 2**31 + 12345   # the driver's seeds pass 32 signed bits


def test_poisson_schedule_repeats_and_seeds_share_the_work():
    a = traffic.poisson_gaps(BIG_SEED, 150.0, 4500)
    b = traffic.poisson_gaps(BIG_SEED, 150.0, 4500)
    c = traffic.poisson_gaps(7, 150.0, 4500)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(np.sort(a), np.sort(c))       # same gaps, other order
    assert abs(a.sum() - 30.0) < 0.05                   # the window's seconds
    assert abs(a.std() / a.mean() - 1.0) < 0.05         # exponential: cv = 1


def test_rows_use_every_picture_equally():
    rows = traffic.balanced_rows(BIG_SEED, 64, 4480)
    assert np.array_equal(rows, traffic.balanced_rows(BIG_SEED, 64, 4480))
    assert set(np.bincount(rows, minlength=64)) == {70}


def test_pictures_repeat_and_stamps_keep_the_pixels():
    from PIL import Image

    params = {"pool": 4, "side_min": 80, "side_max": 120, "formats": ["jpeg", "png"],
              "jpeg_quality": 92, "png_level": 1}
    pool = pictures.encoded_pool(BIG_SEED, params)
    assert pool == pictures.encoded_pool(BIG_SEED, params)
    assert pool != pictures.encoded_pool(BIG_SEED + 1, params)
    assert [f for f, _ in pool] == ["jpeg", "png", "jpeg", "png"]
    for fmt, data in pool:
        one = pictures.stamp(fmt, data, b"note-1")
        two = pictures.stamp(fmt, data, b"note-2")
        assert one != two and one != data
        want = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
        for stamped in (one, two):
            got = np.asarray(Image.open(io.BytesIO(stamped)).convert("RGB"))
            assert np.array_equal(got, want)
    t = pictures.tensor_pool(BIG_SEED, 3, (32, 48, 3))
    assert t.shape == (3, 32, 48, 3) and t.dtype == np.uint8
    assert np.array_equal(t, pictures.tensor_pool(BIG_SEED, 3, (32, 48, 3)))


def test_tensor_body_is_the_servers_wire():
    from kubernetes_deep_learning_tpu.serving import protocol

    images = pictures.tensor_pool(1, 2, (8, 8, 3))
    body = traffic.encode_tensor_body(images)
    assert body == protocol.encode_predict_request(images)
    assert np.array_equal(
        protocol.decode_predict_request(body, protocol.MSGPACK_CONTENT_TYPE), images)
