"""In-flash image encryption (paper §6.2): bulk XOR with a key.

Stores image bitplanes and the keystream as aligned MLC shared pages through
a :class:`repro.api.ComputeSession` and encrypts *inside the flash array*
(one SBR-based XOR sense per page pair), then decrypts the same way and
verifies round-trip bit-exactness.  End-to-end on the functional device
simulator + Pallas kernels.

    PYTHONPATH=src python examples/image_encryption.py
"""
import numpy as np

from repro.api import ComputeSession
from repro.compile_cache import enable_compile_cache
from repro.flash import image_encryption, speedup_table

enable_compile_cache()

rng = np.random.default_rng(7)
sess = ComputeSession(backend="pallas", seed=7)

# one 128x128 8-bit grayscale image -> exactly one 16 kB page of bits
img = rng.integers(0, 256, (128, 128), dtype=np.uint8)
bits = np.unpackbits(img.reshape(-1))                  # 131072 bits
key = rng.integers(0, 2, bits.shape[0], dtype=np.uint8)

img_v, key_v = sess.write_pair("img", bits, "key", key)
cipher = np.asarray(sess.materialize(img_v ^ key_v, unpacked=True, to_host=False))
assert not np.array_equal(cipher, bits), "ciphertext must differ from plaintext"

# decrypt: XOR the ciphertext with the key again (write back, sense again)
sess2 = ComputeSession(backend="pallas", seed=8)
cipher_v, key_v2 = sess2.write_pair("cipher", cipher, "key", key)
plain = np.asarray(sess2.materialize(cipher_v ^ key_v2, unpacked=True, to_host=False))
np.testing.assert_array_equal(plain, bits)
rec = np.packbits(plain).reshape(128, 128)
np.testing.assert_array_equal(rec, img)
print("round-trip in-flash XOR encryption: bit-exact OK")
print(f"simulated die time: {sess.ledger.makespan_us():.0f} us "
      f"(serial {sess.ledger.serial_us():.0f} us), "
      f"energy {sess.ledger.energy_uj:.0f} uJ, "
      f"plan cache {sess.stats()['plan_cache']}")

s = speedup_table(image_encryption(5000))["speedup_vs"]
print(f"\nprojected speedups at 5k images (Fig 10b): "
      f"OSC {s['osc']:.1f}x  ISC {s['isc']:.1f}x  ParaBit {s['parabit']:.2f}x  "
      f"Flash-Cosmos {s['flashcosmos']:.2f}x")
